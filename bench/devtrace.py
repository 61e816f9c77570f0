"""Reduction of a profiler trace to what the per-layer metrics read.

The JAX profiler writes an XSpace (``*.xplane.pb``). Each accelerator
is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per operation run on the device and its ``XLA Modules`` line one
event per program execution (a launch). The host plane ``/host:CPU``
has a line per thread; the thread that ran the benchmark holds its own
annotations, ``bench.window`` around the measured window and
``bench.study`` around each study, nested with the Python calls and
runtime events of that thread. An event's stats (a span's keyword
arguments) are kept beside it in ``Trace.args``. All times are
nanoseconds on the profiler's one clock.
"""

from __future__ import annotations

import dataclasses
import warnings

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass
class Device:
    name: str
    ops: list  # (start_ns, end_ns, name)
    modules: list  # (start_ns, end_ns, name)


@dataclasses.dataclass
class Trace:
    window: tuple  # (start_ns, end_ns) of bench.window
    studies: list  # (start_ns, end_ns) of each bench.study
    devices: list  # Device, in device order
    host: list  # (start_ns, end_ns, name) of each event of the benchmark's thread
    #: the arguments of each event of ``host``, in the same order: a span's
    #: keyword arguments (``rows``, ``width``, ...) as {name: value}
    args: list = dataclasses.field(default_factory=list)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name) for e in line.events]


def op_name(name: str) -> str:
    """An XLA op event is named by its whole HLO instruction; keep the
    instruction's name (``%fusion.7 = u32[81] fusion(...)`` -> ``fusion.7``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def from_profile(profile) -> Trace:
    """Build a ``Trace`` from a ``jax.profiler.ProfileData``."""
    devices, host, args = [], [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(Device(
                plane.name,
                _events(lines[OPS_LINE]) if OPS_LINE in lines else [],
                _events(lines[MODULES_LINE]) if MODULES_LINE in lines else [],
            ))
        elif plane.name == HOST_PLANE:
            for ln in plane.lines:
                events = _events(ln)
                if any(n == "bench.window" for _, _, n in events):
                    host.extend(events)
                    with warnings.catch_warnings():
                        # the binding's stats type warns on first use; where
                        # warnings are errors, that aborts the process
                        warnings.filterwarnings(
                            "ignore", "builtin type event_stats", DeprecationWarning)
                        args.extend(dict(e.stats) for e in ln.events)
    devices.sort(key=lambda d: int(d.name[len(DEVICE_PREFIX):].split()[0]))
    windows = [(s, e) for s, e, n in host if n == "bench.window"]
    if len(windows) != 1:
        raise ValueError(f"expected one bench.window span, found {len(windows)}")
    studies = sorted((s, e) for s, e, n in host if n == "bench.study")
    return Trace(windows[0], studies, devices, host, args)


def load(path) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(str(path)))


def union(intervals, lo: float, hi: float) -> list:
    """Disjoint sorted (start, end) cover of ``intervals`` clipped to
    [lo, hi]."""
    out = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_ns(device: Device, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which some operation ran on ``device``."""
    return sum(e - s for s, e in union(device.ops, lo, hi))


def idle_gaps(device: Device, lo: float, hi: float) -> list:
    """(start, end) of every stretch of [lo, hi] with no operation."""
    gaps, t = [], lo
    for s, e in union(device.ops, lo, hi):
        if s > t:
            gaps.append((t, s))
        t = e
    if hi > t:
        gaps.append((t, hi))
    return gaps


def host_activity(host: list, instants: list) -> list:
    """What the benchmark's thread was doing at each of the sorted
    ``instants``: the innermost ``bench.*`` span and the innermost event
    covering it. The thread's events nest, so one sweep with a stack of
    the open events answers all instants."""
    events = sorted(host, key=lambda x: (x[0], -x[1]))
    names, stack, j = [], [], 0
    for t in instants:
        while j < len(events) and events[j][0] <= t:
            while stack and stack[-1][1] < events[j][0]:
                stack.pop()
            stack.append(events[j])
            j += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        if not stack:
            names.append("no host event")
            continue
        inner = stack[-1][2]
        outer = next((n for _, _, n in reversed(stack) if n.startswith("bench.")),
                     "outside bench spans")
        names.append(outer if inner == outer else f"{outer} > {inner}")
    return names


#: idle stretches shorter than this are the seams between the operations
#: of one program, counted together rather than named one by one
SHORT_GAP_NS = 10_000


def breakdown(trace: Trace, device: Device, top: int = 10) -> dict:
    """The device operations that took most time and the idle time by
    what the benchmark's thread was doing in the middle of each gap,
    within the window, each as [name, seconds]."""
    lo, hi = trace.window
    ops: dict[str, float] = {}
    for s, e, n in device.ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            n = op_name(n)
            ops[n] = ops.get(n, 0.0) + (e - s)
    all_gaps = idle_gaps(device, lo, hi)
    long_gaps = [(s, e) for s, e in all_gaps if e - s >= SHORT_GAP_NS]
    short = sum(e - s for s, e in all_gaps if e - s < SHORT_GAP_NS)
    gaps: dict[str, float] = {}
    if short:
        gaps["gaps under 10 us (between operations)"] = short
    names = host_activity(trace.host, [(s + e) / 2 for s, e in long_gaps])
    for (s, e), n in zip(long_gaps, names):
        gaps[n] = gaps.get(n, 0.0) + (e - s)

    def rank(d):
        return [[n, v * 1e-9] for n, v in sorted(d.items(), key=lambda x: -x[1])[:top]]

    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}
