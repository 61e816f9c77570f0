"""The fetch of each search launch's results: one device->host copy.

The jitted (R, C) search, single-device and sharded, returns r, c and
tau stacked inside its own program as one (3, rows) int64 array. Each
launch's ``repro.search.fetch`` span reads that one array back
(``copies`` 1), and the benchmark's ``dispatch.host_copies`` sums the
argument. The results are the numpy backend's, bit for bit.
"""

import pathlib
import sys

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData, TraceAnnotation

from conftest import REPO, run_multidevice
from repro.core import engine
from repro.core.study import Study
from repro.parallel import shard_eval

BENCH = pathlib.Path(REPO) / "bench"
sys.path.append(str(BENCH))  # the benchmark's trace readers

import devtrace  # noqa: E402
import harness  # noqa: E402

FIG7 = {
    "workload": {"kind": "gemms",
                 "gemms": [[16, 64, 16], [37, 300, 64], [100, 1000, 128]]},
    "space": {"mac_budgets": [256, 1024], "tiers": [1, 2, 3, 4],
              "dataflow": "dos", "tech": "tsv", "mode": "opt"},
    "analysis": {"kind": "sweep", "figure": "fig7", "backend": "jax", "chunk": 8},
}


def _batch(width, B, seed=0):
    """(D1, D2, Tser, budget) whose searched width rounds up to ``width``."""
    rng = np.random.default_rng(seed)
    D1 = rng.integers(width // 2 + 1, width + 1, B)
    D1[0] = width
    D2 = rng.integers(1, 4097, B)
    Tser = rng.integers(1, 4097, B)
    budget = np.full(B, max(width, 1 << 14), dtype=np.int64)
    return D1, D2, Tser, budget


@pytest.mark.parametrize("program", ["single", "sharded"])
def test_search_program_returns_one_stacked_array(program):
    a = np.ones(5, dtype=np.int64)
    with jax.enable_x64(True):
        fn = (engine._jax_search_fn(8) if program == "single"
              else shard_eval._sharded_search_fn(1, 8))
        out = fn.lower(a, a, a, a).out_info
    assert out.shape == (3, 5) and out.dtype == np.int64


@pytest.mark.parametrize("launches, chunk", [(1, None), (1, 50), (2, 25), (5, 10)])
@pytest.mark.parametrize("width", [8, 512])
def test_jax_batch_equals_numpy_over_launches(launches, chunk, width, monkeypatch):
    batch = _batch(width, 50, seed=width)
    copies = []
    fetch = engine._fetch

    def spy(out, rows):
        copies.append(1 if isinstance(out, jax.Array) else len(out))
        return fetch(out, rows)

    monkeypatch.setattr(engine, "_fetch", spy)
    got = engine._search_batch(*batch, "jax", chunk)
    assert copies == [1] * launches
    want = engine._search_batch(*batch, "numpy", None)
    for x, y in zip(got, want):
        assert x.dtype == np.int64
        np.testing.assert_array_equal(x, y)


def test_fetch_reads_a_tuple_of_host_arrays_as_three_copies(monkeypatch):
    """A search that hands back r, c and tau as three host arrays (as a
    wrapped search may) gives the same (3, rows) results, counted as
    three copies."""
    seen = []
    real = engine.TraceAnnotation

    def spy(name, **kw):
        seen.append((name, kw))
        return real(name, **kw)

    rows = np.arange(12, dtype=np.int64).reshape(3, 4)
    monkeypatch.setattr(engine, "TraceAnnotation", spy)
    np.testing.assert_array_equal(engine._fetch(tuple(rows), 4), rows)
    np.testing.assert_array_equal(engine._fetch(jax.numpy.asarray(rows), 4), rows)
    assert seen == [("repro.search.fetch", {"rows": 4, "copies": 3}),
                    ("repro.search.fetch", {"rows": 4, "copies": 1})]


def test_host_copies_counts_one_copy_per_launch(tmp_path):
    """Traced as the benchmark traces a study: the reader sums the fetch
    spans' ``copies`` (one per launch) per study."""
    study = Study.from_dict(FIG7)
    study.run()  # compiles
    calls = []
    search = engine._search_batch

    def spy(D1, D2, Tser, budget, backend, chunk, n_shards=1):
        calls.append(-(-D1.shape[0] // chunk))
        return search(D1, D2, Tser, budget, backend, chunk, n_shards)

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(engine, "_search_batch", spy)
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            with TraceAnnotation("bench.window"):
                for _ in range(2):
                    with TraceAnnotation("bench.study"):
                        study.run()
        finally:
            jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    trace = devtrace.from_profile(ProfileData.from_file(str(path)))
    read = harness.load_reader("dispatch.host_copies")
    assert len(trace.studies) == 2 and sum(calls) > 2
    assert read(trace) == sum(calls) / 2
    bare = devtrace.Trace(trace.window, trace.studies, trace.devices, trace.host,
                          [{k: v for k, v in a.items() if k != "copies"}
                           for a in trace.args])
    assert read(bare) is None


def test_sharded_fetch_is_one_copy_and_matches_unsharded():
    """Four fake CPU devices: the sharded program's stacked output is
    split along the rows; batches that leave shards padded (1 and 3
    rows, a last step of 10 rows over 4 chips) equal the unsharded and
    numpy searches bit for bit, each step fetched in one copy."""
    out = run_multidevice(
        """
        import numpy as np, jax
        from jax.sharding import PartitionSpec as P
        from repro.core import engine
        from repro.parallel import shard_eval

        assert jax.local_device_count() == 4
        a = np.ones(8, dtype=np.int64)
        with jax.enable_x64(True):
            lowered = shard_eval._sharded_search_fn(4, 8).lower(a, a, a, a)
        info = lowered.out_info
        assert info.shape == (3, 8) and info.dtype == np.int64, info
        sharding = lowered.compile().output_shardings
        assert sharding.spec == P(None, "shard"), sharding

        fetches = []
        real = shard_eval.TraceAnnotation

        def spy(name, **kw):
            if name == "repro.search.fetch":
                fetches.append(kw)
            return real(name, **kw)

        shard_eval.TraceAnnotation = spy
        rng = np.random.default_rng(1)
        for width, B, chunk in [(512, 1, None), (512, 3, None), (64, 50, 5)]:
            D1 = rng.integers(width // 2 + 1, width + 1, B)
            D1[0] = width
            b = (D1, rng.integers(1, 4097, B), rng.integers(1, 4097, B),
                 np.full(B, 1 << 14, dtype=np.int64))
            fetches.clear()
            got = engine._search_batch(*b, "jax", chunk, 4)
            step = (chunk or (1 << 23) // width) * 4
            assert fetches == [{"rows": min(step, B - lo), "copies": 1}
                               for lo in range(0, B, step)], fetches
            for ref in (engine._search_batch(*b, "jax", chunk, 1),
                        engine._search_batch(*b, "numpy", None)):
                for x, y in zip(got, ref):
                    assert x.dtype == np.int64 and np.array_equal(x, y)
        print("fetch-ok")
        """,
        n_devices=4,
    )
    assert "fetch-ok" in out
