"""Rows per device launch of the (R, C) search.

``chunk`` is rows per launch. Left ``None`` it is derived from the width
the batch searches (the power of two ``_search_batch`` compiles for):
``2**23 // width`` rows on the jax backend, ``clip(2**23 // width, 64,
2048)`` on numpy, where the width is the widest ``min(D1, budget)``. The
sharded path takes that many rows per device. No geometry changes a bit
of the result.
"""

import json
import math

import numpy as np
import pytest

from conftest import run_multidevice
from repro.core import engine
from repro.core.study import Study

DECODE = {
    "workload": {"kind": "network", "arch": "deepseek-moe-16b", "shape": "decode_32k"},
    "space": {"mac_budgets": [16384, 65536, 262144], "tiers": list(range(1, 17)),
              "dataflow": "dos", "tech": "tsv", "mode": "opt"},
    "analysis": {"kind": "schedule", "backend": "jax"},
}


@pytest.fixture
def launches(monkeypatch):
    """Rows of every launch, one list per ``_search_batch`` call."""
    calls = []
    search, jax_fn = engine._search_batch, engine._jax_search_fn
    rc, tables = engine._search_rc, engine._search_from_tables

    def spy_batch(*args, **kwargs):
        calls.append([])
        return search(*args, **kwargs)

    def spy_jax_fn(r_max):
        fn = jax_fn(r_max)

        def launch(D1, *rest):
            calls[-1].append(D1.shape[0])
            return fn(D1, *rest)

        return launch

    def spy_rc(xp, D1, *rest):
        if xp is np:  # the jax kernel calls it too, as it is traced
            calls[-1].append(D1.shape[0])
        return rc(xp, D1, *rest)

    def spy_tables(t, sel, *rest):
        calls[-1].append(sel.shape[0])
        return tables(t, sel, *rest)

    monkeypatch.setattr(engine, "_search_batch", spy_batch)
    monkeypatch.setattr(engine, "_jax_search_fn", spy_jax_fn)
    monkeypatch.setattr(engine, "_search_rc", spy_rc)
    monkeypatch.setattr(engine, "_search_from_tables", spy_tables)
    return calls


def _batch(width, B, seed=0):
    """(D1, D2, Tser, budget) whose searched width rounds up to ``width``."""
    rng = np.random.default_rng(seed)
    D1 = rng.integers(width // 2 + 1, width + 1, B)
    D1[0] = width
    D2 = rng.integers(1, 4097, B)
    Tser = rng.integers(1, 4097, B)
    budget = np.full(B, max(width, 1 << 14), dtype=np.int64)
    return D1, D2, Tser, budget


def _schedule(spec, **analysis):
    spec = json.loads(json.dumps(spec))
    spec["analysis"].update(analysis)
    return json.dumps(Study.from_dict(spec).run().to_dict()["payload"], sort_keys=True)


def test_decode_schedule_makes_one_launch_per_search(launches):
    """The decode cell's schedule: batch 128 searches width 128 while its
    logits GEMM is vocabulary-wide, so sizing from the widest GEMM
    dimension would launch 81 rows at a time (20 launches)."""
    _schedule(DECODE)
    assert [len(c) for c in launches] == [1, 1, 1]
    rows = [c[0] for c in launches]
    assert rows == [336, 21, 1127]
    by_widest_gemm = (1 << 23) // min(102400, 262144)
    assert sum(-(-b // by_widest_gemm) for b in rows) == 20


@pytest.mark.parametrize(
    "width, B, backend, rows",
    [
        (1 << 15, 300, "jax", [256, 44]),
        (1 << 15, 300, "numpy", [256, 44]),
        (8, 2100, "jax", [2100]),
        (8, 2100, "numpy", [2048, 52]),  # numpy keeps the 2048-row cap
    ],
)
def test_rows_per_launch_follow_the_searched_width(width, B, backend, rows, launches):
    batch = _batch(width, B)
    got = engine._search_batch(*batch, backend, None)
    assert launches == [rows]
    assert sum(rows) == B
    if backend == "jax":
        want = engine._search_batch(*batch, "numpy", None)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)


def test_explicit_chunk_keeps_its_rows(launches):
    """``chunk=81`` still means 81 rows a launch, and the payload equals
    the derived geometry's and the numpy backend's bit for bit."""
    chunked = _schedule(DECODE, chunk=81)
    sizes = [sum(c) for c in launches]
    assert [len(c) for c in launches] == [math.ceil(b / 81) for b in sizes] == [5, 1, 14]
    assert all(c == [81] * (len(c) - 1) + [b - 81 * (len(c) - 1)]
               for c, b in zip(launches, sizes))
    del launches[:]
    derived = _schedule(DECODE)
    assert [len(c) for c in launches] == [1, 1, 1]
    assert chunked == derived == _schedule(DECODE, backend="numpy")


def test_sharded_steps_take_rows_times_shards():
    """On fake CPU devices a sharded step holds rows x n_shards: an
    explicit chunk's, and the width rule's (2**23 // 8192 = 1024 rows a
    device at width 8192), bit-identical to the unsharded search."""
    out = run_multidevice(
        """
        import numpy as np
        from repro.core import engine
        from repro.parallel import shard_eval

        steps = []
        real = shard_eval.sharded_search

        def spy(D1, *rest):
            steps.append(D1.shape[0])
            return real(D1, *rest)

        shard_eval.sharded_search = spy
        rng = np.random.default_rng(0)

        def batch(width, B):
            D1 = rng.integers(width // 2 + 1, width + 1, B)
            D1[0] = width
            return (D1, rng.integers(1, 4097, B), rng.integers(1, 4097, B),
                    np.full(B, 1 << 14, dtype=np.int64))

        for width, B, chunk, n_shards, want in [
            (512, 50, 5, 4, [20, 20, 10]),
            (8192, 2100, None, 2, [2048, 52]),
        ]:
            b = batch(width, B)
            steps.clear()
            got = engine._search_batch(*b, "jax", chunk, n_shards)
            assert steps == want, (steps, want)
            ref = engine._search_batch(*b, "jax", chunk, 1)
            for x, y in zip(got, ref):
                assert np.array_equal(x, y)
            ref = engine._search_batch(*b, "numpy", None)
            for x, y in zip(got, ref):
                assert np.array_equal(x, y)
        print("steps-ok")
        """,
        n_devices=4,
    )
    assert "steps-ok" in out
