"""The readers of the program's spans, on a small trace whose every
number is counted by hand below.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench

Times in milliseconds (the trace holds nanoseconds):

    bench.window [0, 100]; bench.study [5, 60] and [62, 110]
    repro.study [6, 59]
        repro.lower [7, 9]
        repro.evaluate [10, 40]
            repro.search [11, 30]
                repro.search.fetch [15, 20], [25, 28]
            repro.price [31, 35]; repro.thermal [35, 38]
        repro.select [41, 45], holding np.argmin [42, 44]
    repro.study [63, 110]
        repro.search [64, 105]
            repro.search.fetch [70, 80], [95, 105]
    repro.lower [120, 125], after the window
    TPU:0 ops [12, 17], [26, 27], [71, 75], [90, 96]; TPU:1 busy [0, 100]

Clipped to the window, the second study ends at 100, its search at 100
and its last fetch is [95, 100].
"""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import devtrace  # noqa: E402
import harness  # noqa: E402
import spans  # noqa: E402

MS = 1_000_000

HOST = [
    (0, 100, "bench.window"), (5, 60, "bench.study"), (62, 110, "bench.study"),
    (6, 59, "repro.study"), (7, 9, "repro.lower"), (10, 40, "repro.evaluate"),
    (11, 30, "repro.search"), (15, 20, "repro.search.fetch"),
    (25, 28, "repro.search.fetch"), (31, 35, "repro.price"),
    (35, 38, "repro.thermal"), (41, 45, "repro.select"), (42, 44, "np.argmin"),
    (63, 110, "repro.study"), (64, 105, "repro.search"),
    (70, 80, "repro.search.fetch"), (95, 105, "repro.search.fetch"),
    (120, 125, "repro.lower"),
]
OPS = [(12, 17), (26, 27), (71, 75), (90, 96)]


def _ns(intervals):
    return [(s * MS, e * MS, *rest) for s, e, *rest in intervals]


@pytest.fixture
def trace():
    return devtrace.Trace(
        window=(0, 100 * MS),
        studies=_ns([(5, 60), (62, 110)]),
        devices=[
            devtrace.Device("/device:TPU:0", _ns([(s, e, "fusion.1") for s, e in OPS]), []),
            devtrace.Device("/device:TPU:1", _ns([(0, 100, "fusion.1")]), []),
        ],
        host=_ns(HOST),
    )


def _read(name, trace):
    return harness.load_reader(name)(trace)


def test_intervals_are_clipped_to_the_window(trace):
    assert spans.intervals(trace, "repro.search.fetch") == _ns(
        [(15, 20), (25, 28), (70, 80), (95, 100)])
    assert spans.intervals(trace, "repro.search") == _ns([(11, 30), (64, 100)])
    # the lowering after the window is dropped
    assert spans.intervals(trace, "repro.lower") == _ns([(7, 9)])
    assert spans.intervals(trace, "repro.nothing") == []


def test_overlap_of_two_covers():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 45)]
    assert spans.overlap(a, b) == 5 + 5 + 2 + 5
    assert spans.overlap(a, []) == 0
    assert spans.total(a) == 30


def test_self_time_leaves_out_nested_program_spans(trace):
    # search: 19 - (5 + 3) in the first study, 36 - (10 + 5) in the second
    assert spans.self_ns(trace, "repro.search") == 32 * MS
    # evaluate: 30 less the search, price and thermal in it
    assert spans.self_ns(trace, "repro.evaluate") == (30 - 19 - 4 - 3) * MS
    # a nested event that is not a program span stays in the self time
    assert spans.self_ns(trace, "repro.select") == 4 * MS
    assert spans.self_ns(trace, "repro.search.fetch") == 23 * MS
    assert spans.self_ns(trace, "repro.nothing") is None


def test_idle_inside_spans(trace):
    dev0, dev1 = trace.devices
    # fetch cover 23 ms, of which TPU:0 is busy [15, 17], [26, 27],
    # [71, 75], [95, 96]: 8 ms
    assert spans.idle_ns(trace, dev0, "repro.search.fetch") == 15 * MS
    assert spans.idle_ns(trace, dev1, "repro.search.fetch") == 0
    assert spans.idle_ns(trace, dev0, "repro.nothing") is None


def test_unspanned_time(trace):
    # studies 55 + 38 ms; layers cover [7, 9], [11, 30], [31, 38],
    # [41, 45], [64, 100]: 68 ms
    assert spans.unspanned_ns(trace) == 25 * MS


def test_metric_readers(trace):
    # per study: the window holds 2
    assert _read("lowering.host_ms", trace) == pytest.approx(1.0)
    assert _read("dispatch.host_ms", trace) == pytest.approx(16.0)
    # 15 ms idle on TPU:0, none on TPU:1: 7.5 ms a chip
    assert _read("dispatch.fetch_idle_ms", trace) == pytest.approx(3.75)
    assert _read("select.host_ms", trace) == pytest.approx(2.0)
    assert _read("pricing.host_ms", trace) == pytest.approx(2.0)
    assert _read("thermal.host_ms", trace) == pytest.approx(1.5)
    assert _read("host.unspanned_ms", trace) == pytest.approx(12.5)


READERS = ("lowering.host_ms", "dispatch.host_ms", "dispatch.fetch_idle_ms",
           "select.host_ms", "pricing.host_ms", "thermal.host_ms",
           "host.unspanned_ms")


@pytest.mark.parametrize("name", READERS)
def test_readers_find_nothing_in_a_program_without_spans(trace, name):
    bare = devtrace.Trace(trace.window, trace.studies, trace.devices,
                          [x for x in trace.host if not x[2].startswith("repro.")])
    assert _read(name, bare) is None


def test_fetch_idle_finds_nothing_without_a_device(trace):
    bare = devtrace.Trace(trace.window, trace.studies, [], trace.host)
    assert _read("dispatch.fetch_idle_ms", bare) is None


def test_every_reader_has_a_benchmark_entry():
    import json

    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    names = {m["name"] for m in spec["per_layer"]}
    assert set(READERS) <= names


def test_program_spans_reach_the_trace():
    """``devtrace.from_profile`` keeps the program's spans: they share
    the benchmark's thread line."""
    from jax.profiler import ProfileData

    names = {1: "bench.window", 2: "bench.study", 3: "repro.study",
             4: "repro.search", 5: "repro.search.fetch"}
    events = [(1, 0, 21), (2, 1, 6), (3, 1, 6), (4, 2, 5), (5, 3, 4)]
    unit = 10_000_000  # picoseconds in 10 us
    body = " ".join(
        f"events {{ metadata_id: {k} offset_ps: {s * unit} duration_ps: {(e - s) * unit} }}"
        for k, s, e in events)
    meta = " ".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }}'
        for k, n in names.items())
    text = (f'planes {{ id: 1 name: "/host:CPU" lines {{ id: 1 name: "python" '
            f'timestamp_ns: 0 {body} }} {meta} }}')
    trace = devtrace.from_profile(ProfileData.from_text_proto(text))
    assert spans.intervals(trace, "repro.search.fetch") == [(30_000, 40_000)]
    assert spans.self_ns(trace, "repro.search") == 20_000
    assert spans.unspanned_ns(trace) == 20_000
