"""The program's own spans in a profiler trace.

``Study.run`` opens ``jax.profiler.TraceAnnotation`` spans named
``repro.*`` at each layer boundary of a study (lowering, search, the
device->host fetch of each launch, candidate selection, pricing,
thermal). They land on the benchmark's thread line beside
``bench.study``, so ``devtrace.from_profile`` already holds them in
``Trace.host``, and their keyword arguments (``rows``, ``width``,
``launches``, ``candidates``, ``points``) in ``Trace.args``. A program
without them gives the readers nothing to read: the span readers here
return ``None`` then, never a zero.
"""

from __future__ import annotations

import bisect

import devtrace

PREFIX = "repro."

#: the layer spans; what lies in ``bench.study`` outside all of them is
#: the front door's own time
LAYERS = ("repro.lower", "repro.search", "repro.search.fetch", "repro.price",
          "repro.thermal", "repro.select")


def intervals(trace, name: str) -> list:
    """Sorted (start, end) of every span named ``name``, clipped to the
    window; spans wholly outside it are dropped."""
    lo, hi = trace.window
    out = []
    for s, e, n in trace.host:
        if n == name:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                out.append((s, e))
    return sorted(out)


def arg_total(trace, name: str, key: str) -> float | None:
    """Sum of the argument ``key`` over the spans named ``name`` that
    lie in the window. ``None`` where no such span carries it."""
    lo, hi = trace.window
    values = [a[key] for (s, e, n), a in zip(trace.host, trace.args)
              if n == name and key in a and min(e, hi) > max(s, lo)]
    return sum(values) if values else None


def total(cover: list) -> float:
    return sum(e - s for s, e in cover)


def overlap(a: list, b: list) -> float:
    """Length of the intersection of two disjoint sorted covers."""
    out, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def self_ns(trace, name: str) -> float | None:
    """Summed self time of the spans named ``name`` within the window:
    each one's duration less the union of the ``repro.*`` spans nested
    in it. ``None`` where no span has that name."""
    lo, hi = trace.window
    spans = []
    for s, e, n in trace.host:
        if n.startswith(PREFIX):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                spans.append((s, e, n))
    spans.sort()
    starts = [s for s, _, _ in spans]
    found, out = False, 0.0
    for i, (s, e, n) in enumerate(spans):
        if n != name:
            continue
        found = True
        j0, j1 = bisect.bisect_left(starts, s), bisect.bisect_right(starts, e)
        inner = [spans[j] for j in range(j0, j1) if j != i and spans[j][1] <= e]
        out += (e - s) - total(devtrace.union(inner, s, e))
    return out if found else None


def idle_ns(trace, device, name: str) -> float | None:
    """Time inside the union of the spans named ``name`` in which no
    operation ran on ``device``. ``None`` where no span has that name."""
    lo, hi = trace.window
    cover = devtrace.union(intervals(trace, name), lo, hi)
    if not cover:
        return None
    return total(cover) - overlap(cover, devtrace.union(device.ops, lo, hi))


def unspanned_ns(trace) -> float | None:
    """Time inside ``bench.study`` covered by none of the ``LAYERS``
    spans. ``None`` where the program opened no ``repro.study`` span."""
    if not intervals(trace, "repro.study"):
        return None
    lo, hi = trace.window
    studies = devtrace.union(trace.studies, lo, hi)
    layers = devtrace.union([x for x in trace.host if x[2] in LAYERS], lo, hi)
    return total(studies) - overlap(studies, layers)


def per_study_ms(trace, ns: float | None) -> float | None:
    """``ns`` over the window's studies, in milliseconds."""
    if ns is None or not trace.studies:
        return None
    return ns * 1e-6 / len(trace.studies)
