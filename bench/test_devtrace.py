"""The reduction from trace to per-layer metrics, on a small trace whose
every number is counted by hand below.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q bench

Times in units of 10 us (the proto holds picoseconds):

    host    bench.window [0, 21]; bench.study [1, 6] and [9, 18];
            repro.search [2, 5] (rows 81, width 128); np.argmin [15, 18]
    TPU:0   ops fusion.1 [1, 3], copy.2 [2, 4], fusion.1 [11, 13],
            fusion.1 [13.4, 14]; modules jit_run [1, 4], [11, 14]
    TPU:1   ops fusion.1 [1, 11]

TPU:0 is busy [1, 4], [11, 13] and [13.4, 14]: 5.6 units; idle [0, 1],
[4, 11], [13, 13.4] (4 us, a short gap) and [14, 21]. TPU:1 is busy 10
units. Window 21 units.
"""

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import devtrace  # noqa: E402
import harness  # noqa: E402

UNIT = 10_000_000  # picoseconds in 10 us


STATS = {"rows": 1, "width": 2}


def _ev(meta, start, end, **stats):
    st = "".join(f"stats {{ metadata_id: {STATS[k]} int64_value: {v} }} "
                 for k, v in stats.items())
    return (f"events {{ metadata_id: {meta} offset_ps: {round(start * UNIT)} "
            f"duration_ps: {round((end - start) * UNIT)} {st}}}")


def _plane(pid, name, lines, names):
    body = "".join(
        f'lines {{ id: {i + 1} name: "{ln}" timestamp_ns: 0 {" ".join(evs)} }} '
        for i, (ln, evs) in enumerate(lines)
    )
    meta = "".join(
        f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }} '
        for k, n in names.items()
    )
    stats = "".join(
        f'stat_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }} '
        for n, k in STATS.items()
    )
    return f'planes {{ id: {pid} name: "{name}" {body} {meta} {stats} }}'


TEXT = " ".join([
    _plane(1, "/host:CPU", [("python", [
        _ev(1, 0, 21), _ev(2, 1, 6), _ev(4, 2, 5, rows=81, width=128), _ev(2, 9, 18),
        _ev(3, 15, 18)])],
        {1: "bench.window", 2: "bench.study", 3: "np.argmin", 4: "repro.search"}),
    _plane(2, "/device:TPU:0", [
        ("XLA Ops", [_ev(1, 1, 3), _ev(2, 2, 4), _ev(1, 11, 13), _ev(1, 13.4, 14)]),
        ("XLA Modules", [_ev(3, 1, 4), _ev(3, 11, 14)])],
        {1: "%fusion.1 = u32[81]{0} fusion(u32[81,128]{1,0} %p), kind=kLoop",
         2: "%copy.2 = u32[81]{0} copy(u32[81]{0} %q)", 3: "jit_run(8794365689757704795)"}),
    _plane(3, "/device:TPU:1", [("XLA Ops", [_ev(1, 1, 11)])], {1: "fusion.1"}),
])


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return devtrace.from_profile(ProfileData.from_text_proto(TEXT))


def test_structure(trace):
    assert trace.window == (0, 210_000)
    assert trace.studies == [(10_000, 60_000), (90_000, 180_000)]
    assert [d.name for d in trace.devices] == ["/device:TPU:0", "/device:TPU:1"]
    assert len(trace.devices[0].ops) == 4 and len(trace.devices[0].modules) == 2


def test_host_events_keep_their_arguments(trace):
    assert len(trace.args) == len(trace.host)
    got = {n: a for (_, _, n), a in zip(trace.host, trace.args)}
    assert got["repro.search"] == {"rows": 81, "width": 128}
    assert got["bench.window"] == {} and got["np.argmin"] == {}


def test_busy_is_the_union_of_intervals(trace):
    lo, hi = trace.window
    assert devtrace.union(trace.devices[0].ops, lo, hi) == [
        (10_000, 40_000), (110_000, 130_000), (134_000, 140_000)]
    assert devtrace.busy_ns(trace.devices[0], lo, hi) == 56_000
    assert devtrace.busy_ns(trace.devices[1], lo, hi) == 100_000
    # clipped to a sub-window
    assert devtrace.busy_ns(trace.devices[0], 20_000, 120_000) == 30_000
    assert devtrace.idle_gaps(trace.devices[0], lo, hi) == [
        (0, 10_000), (40_000, 110_000), (130_000, 134_000), (140_000, 210_000)]


def test_metric_readers(trace):
    idle = harness.load_reader("device.idle_pct")(trace)
    assert idle == pytest.approx(100.0 * (15.4 + 11) / 42)
    # busiest device: 100 us over 2 studies
    assert harness.load_reader("search.device_ms")(trace) == pytest.approx(0.05)
    # 2 program executions on the first device, 2 studies
    assert harness.load_reader("search.launches")(trace) == 1.0
    # 81 rows searched over 2 studies
    assert harness.load_reader("search.points")(trace) == 40.5


def test_readers_find_nothing_without_a_device(trace):
    empty = devtrace.Trace(trace.window, trace.studies, [], trace.host)
    for name in ("device.idle_pct", "search.device_ms", "search.launches"):
        assert harness.load_reader(name)(empty) is None


def test_breakdown(trace):
    bd = devtrace.breakdown(trace, trace.devices[0])
    assert bd["device_ops"] == [["fusion.1", pytest.approx(46e-6)],
                                ["copy.2", pytest.approx(20e-6)]]
    # [0, 1] and [4, 11] lie in the window only; [14, 21] has its middle
    # (17.5) in the second study, inside np.argmin; [13, 13.4] is short
    assert bd["idle_gaps"] == [
        ["bench.window", pytest.approx(80e-6)],
        ["bench.study > np.argmin", pytest.approx(70e-6)],
        ["gaps under 10 us (between operations)", pytest.approx(4e-6)]]


def test_host_activity_sweep():
    host = [(0, 100, "bench.window"), (10, 50, "bench.study"), (20, 30, "f"),
            (22, 25, "g"), (60, 90, "bench.study"), (70, 80, "h")]
    got = devtrace.host_activity(host, [5, 21, 23, 27, 40, 55, 75, 95, 150])
    assert got == ["bench.window", "bench.study > f", "bench.study > g",
                   "bench.study > f", "bench.study", "bench.window",
                   "bench.study > h", "bench.window", "no host event"]
