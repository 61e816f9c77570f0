"""Guided Pareto search benchmark: a ~1e9-point space to a stable
frontier in seconds, validated against an exhaustive reference.

Pins the PR-6 guided-search story (``core.search``) in three acts:

1. **validation** (~1M-point subspace, exhaustively tractable): run the
   exhaustive reference, then the guided search with a <1% evaluation
   budget — asserts the guided feasible frontier reaches >= 0.99 of the
   exhaustive hypervolume (common reference point), and that identical
   seeds give bit-identical ``StudyResult`` JSON.
2. **resume**: the same guided study chunk-cached cold, then re-run
   warm — asserts the warm run replays every generation from cache with
   **0 recomputed chunks** and an identical payload.
3. **full space** (~1e9 effective points: 2560 MAC budgets x 16 tiers x
   3 dataflows x 2 vlink techs x 64 DRAM x 64 SRAM values): the guided
   search prices a few 10^4 points of it — wall clock and points/s
   reported for 1 worker vs N ``parallel.work_queue`` processes, with
   payload bit-identity asserted across worker counts. The >= 2x
   multi-worker speedup assertion is gated on ``os.cpu_count() >= 4``
   (on fewer cores the honest numbers are still recorded).

Writes ``BENCH_search.json`` (``BENCH_search_smoke.json`` with
``--smoke``, the CI-sized run) next to this file.

Run:  PYTHONPATH=src python -m benchmarks.search_bench [--smoke]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import shutil
import tempfile
import time

import numpy as np

from repro.core.cache import ResultCache
from repro.core.search import exhaustive_frontier, hypervolume
from repro.core.study import (
    AnalysisSpec,
    BandwidthSpec,
    SearchSpec,
    SpaceSpec,
    Study,
    WorkloadSpec,
)
from repro._jax_compat import use_compile_cache

HERE = pathlib.Path(__file__).resolve().parent
GEMMS = ((64, 12100, 147), (512, 784, 128))


def _budgets(n: int) -> tuple[int, ...]:
    return tuple(
        int(x) for x in np.unique(np.round(np.geomspace(2**10, 2**20, n)))
    )


def _study(name, budgets, tiers, dataflow, tech, dram, sram, search: SearchSpec,
           workers=None) -> Study:
    return Study(
        name=name,
        workload=WorkloadSpec(kind="gemms", gemms=GEMMS),
        space=SpaceSpec(mac_budgets=budgets, tiers=tiers, dataflow=dataflow,
                        tech=tech),
        analysis=AnalysisSpec(
            kind="search",
            bandwidth=BandwidthSpec.paper_default(),
            search=dataclasses.replace(
                search,
                dram_gbs=tuple(float(x) for x in dram),
                sram_kib=tuple(float(x) for x in sram),
            ),
            workers=workers,
        ),
    )


def _validation_study(smoke: bool) -> Study:
    if smoke:
        return _study(
            "search-bench-validation-smoke",
            _budgets(24), tuple(range(1, 9)), ("dos", "ws"), ("tsv", "miv"),
            np.geomspace(8, 1024, 4), np.geomspace(32, 4096, 4),
            SearchSpec(objectives=("cycles", "energy_j"), generations=4,
                       population=96, refine=(4, 2, 1, 1)),
        )
    return _study(
        "search-bench-validation",
        _budgets(128), tuple(range(1, 17)), ("dos", "ws", "is"), ("tsv", "miv"),
        np.geomspace(8, 1024, 9), np.geomspace(32, 4096, 9),
        SearchSpec(objectives=("cycles", "energy_j"), generations=10,
                   population=960, refine=(16, 8, 8, 4, 4, 2, 2, 1, 1, 1)),
    )


def _full_study(smoke: bool, workers=None) -> Study:
    if smoke:
        return _study(
            "search-bench-full-smoke",
            _budgets(96), tuple(range(1, 17)), ("dos", "ws", "is"),
            ("tsv", "miv"),
            np.geomspace(8, 1024, 16), np.geomspace(32, 4096, 16),
            SearchSpec(objectives=("cycles", "energy_j"), generations=4,
                       population=512, refine=(8, 4, 2, 1)),
            workers=workers,
        )
    return _study(
        "search-bench-full",
        _budgets(2560), tuple(range(1, 17)), ("dos", "ws", "is"), ("tsv", "miv"),
        np.geomspace(8, 1024, 64), np.geomspace(32, 4096, 64),
        SearchSpec(objectives=("cycles", "energy_j"), generations=12,
                   population=4096, refine=(64, 32, 16, 16, 8, 8, 4, 4, 2, 2, 1, 1)),
        workers=workers,
    )


def _run_full(study: Study, block_cells: int) -> tuple[float, dict]:
    """One cold cached full-space run in a scratch dir; (wall_s, payload)."""
    root = tempfile.mkdtemp(prefix="repro-searchbench-")
    try:
        t0 = time.perf_counter()
        res = study.run(cache=ResultCache(root, block_cells=block_cells))
        dt = time.perf_counter() - t0
        assert res.cache["hits"] == 0, res.cache
        return dt, res.to_dict()["payload"]
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run(smoke: bool = False) -> dict:
    out: dict = {"smoke": smoke, "workloads": [list(g) for g in GEMMS]}

    # -- 1. validation: guided vs exhaustive on a tractable subspace --------
    val = _validation_study(smoke)
    t0 = time.perf_counter()
    ex = exhaustive_frontier(val)
    t_ex = time.perf_counter() - t0
    exF = ex["frontier_objectives"]
    ref = exF.max(axis=0) * 1.1  # common reference: both hv use it
    hv_ex = hypervolume(exF, ref)

    t0 = time.perf_counter()
    guided = val.run()
    t_g = time.perf_counter() - t0
    p = guided.payload
    hv_g = hypervolume(p["frontier_objectives"], ref)
    ratio = hv_g / hv_ex
    min_ratio = 0.95 if smoke else 0.99
    assert ratio >= min_ratio, f"hv ratio {ratio:.5f} < {min_ratio}"
    if not smoke:
        assert p["frac_evaluated"] < 0.01, p["frac_evaluated"]
    deterministic = val.run().to_json() == guided.to_json()
    assert deterministic, "same-seed runs are not bit-identical"
    out["validation"] = {
        "space_size": ex["space_size"],
        "exhaustive_s": t_ex,
        "exhaustive_points_per_s": ex["space_size"] / t_ex,
        "exhaustive_frontier": int(len(exF)),
        "hypervolume_exhaustive": hv_ex,
        "guided_s": t_g,
        "n_evaluated": p["n_evaluated"],
        "frac_evaluated": p["frac_evaluated"],
        "guided_frontier": int(len(p["frontier_objectives"])),
        "hypervolume_guided": hv_g,
        "hypervolume_ratio": ratio,
        "same_seed_bit_identical": deterministic,
    }

    # -- 2. resume: warm cache replays every generation, 0 recomputed ------
    root = tempfile.mkdtemp(prefix="repro-searchbench-")
    try:
        t0 = time.perf_counter()
        cold = val.run(cache=ResultCache(root))
        cold_s = time.perf_counter() - t0
        assert cold.cache["hits"] == 0
        t0 = time.perf_counter()
        warm = val.run(cache=ResultCache(root))
        warm_s = time.perf_counter() - t0
        assert warm.cache["misses"] == 0, warm.cache
        assert warm.to_dict()["payload"] == cold.to_dict()["payload"]
        out["resume"] = {
            "cold_s": cold_s,
            "warm_s": warm_s,
            "chunks": cold.cache["misses"],
            "recomputed_chunks_on_resume": warm.cache["misses"],
            "payload_identical": True,
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)

    # -- 3. full space: 1 worker vs N over the work queue ------------------
    cpus = os.cpu_count() or 1
    n_workers = 2 if smoke else min(4, max(2, cpus))
    full1 = _full_study(smoke, workers=1)
    pop = full1.analysis.search.population
    # split each generation into ~2 blocks per worker so the queue has
    # real parallel grain (chunk keys embed the range: identical layout
    # for both runs, so the N-worker run could even resume the 1-worker
    # cache — here both start cold in scratch dirs)
    block_cells = max(1, pop * len(GEMMS) // (2 * n_workers))
    t_1w, payload_1w = _run_full(full1, block_cells)
    fullN = _full_study(smoke, workers=n_workers)
    t_nw, payload_nw = _run_full(fullN, block_cells)
    assert payload_1w == payload_nw, "worker count changed the payload"
    pf = payload_1w
    speedup = t_1w / t_nw if t_nw else float("inf")
    if not smoke:
        assert pf["space_size"] >= 950_000_000, pf["space_size"]
        if cpus >= 4:
            assert speedup >= 2.0, (
                f"{n_workers}-worker speedup {speedup:.2f}x < 2x on {cpus} cpus"
            )
    out["full_space"] = {
        "space_size": pf["space_size"],
        "n_evaluated": pf["n_evaluated"],
        "frac_evaluated": pf["frac_evaluated"],
        "frontier_size": len(pf["frontier_objectives"]),
        "hypervolume": pf["hypervolume"],
        "cpus": cpus,
        "workers": n_workers,
        "wall_s_1_worker": t_1w,
        "points_per_s_1_worker": pf["n_evaluated"] / t_1w,
        f"wall_s_{n_workers}_workers": t_nw,
        f"points_per_s_{n_workers}_workers": pf["n_evaluated"] / t_nw,
        "speedup_vs_1_worker": speedup,
        "speedup_asserted": (not smoke) and cpus >= 4,
        "payload_identical_across_workers": True,
    }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small spaces, light budgets — the CI smoke step")
    args = ap.parse_args()
    out = run(smoke=args.smoke)
    name = "BENCH_search_smoke.json" if args.smoke else "BENCH_search.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    v, f = out["validation"], out["full_space"]
    t_nw = f[f"wall_s_{f['workers']}_workers"]
    print(
        f"validation: hv ratio {v['hypervolume_ratio']:.4f} at "
        f"{v['frac_evaluated']:.3%} of {v['space_size']:,} points "
        f"(exhaustive {v['exhaustive_s']:.1f}s vs guided {v['guided_s']:.1f}s); "
        f"full space {f['space_size']:,} points: {f['n_evaluated']:,} evals, "
        f"1w {f['wall_s_1_worker']:.1f}s vs {f['workers']}w {t_nw:.1f}s "
        f"({f['speedup_vs_1_worker']:.2f}x)"
    )


if __name__ == "__main__":
    use_compile_cache()
    main()
