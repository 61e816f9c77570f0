"""Million-point sweep benchmark: throughput, bounded RSS, resumability.

Pins the production-scale story of the Study/engine stack on a
Fig-7-style sweep (random workloads x 3 MAC budgets x 16 tier counts,
every point a full (R, C) shape search):

1. **cold**: run the whole sweep chunk-cached into a fresh directory —
   reports wall time, points/s, and the process peak RSS (the streamed
   chunk execution keeps it bounded at any grid size);
2. **resume**: delete half the cached chunks and re-run via the same
   cache — asserts (via the artifact's hit/miss counters) that exactly
   the missing half is recomputed and that the stitched result is
   bit-for-bit identical to the cold run;
3. **warm**: run again fully cached — asserts zero recomputation.

Writes ``BENCH_scale.json`` (or ``BENCH_scale_smoke.json`` with
``--smoke``, the CI-sized run) next to this file.

Run:  PYTHONPATH=src python -m benchmarks.scale_bench [--points 1000000]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import resource
import shutil
import sys
import tempfile
import time

import numpy as np

from repro.core.cache import ResultCache
from repro.core.dse import fig7_study
from repro._jax_compat import use_compile_cache

HERE = pathlib.Path(__file__).resolve().parent
BUDGETS = (2**14, 2**16, 2**18)
MAX_TIERS = 16
POINTS_PER_WORKLOAD = len(BUDGETS) * MAX_TIERS


def _peak_rss_mb() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    # linux reports KiB, macOS bytes
    return ru / 1024.0 if sys.platform != "darwin" else ru / 2**20

def run(points: int, seed: int = 0, shard=None, keep_cache: str | None = None):
    n_workloads = max(1, points // POINTS_PER_WORKLOAD)
    # only the jax backend has a device axis — an explicit shard request
    # on the numpy default would error (and 'auto' would measure nothing)
    study = fig7_study(BUDGETS, n_workloads, seed, MAX_TIERS,
                       backend="jax" if shard else "numpy")
    if shard:
        import dataclasses

        study = dataclasses.replace(
            study, analysis=dataclasses.replace(study.analysis, shard=shard)
        )
    root = pathlib.Path(keep_cache) if keep_cache else pathlib.Path(
        tempfile.mkdtemp(prefix="repro-scale-")
    )
    out = {
        "sweep": f"{n_workloads} workloads x {len(BUDGETS)} budgets x {MAX_TIERS} tiers",
        "points": n_workloads * POINTS_PER_WORKLOAD,
    }
    # ~16 chunks at any sweep size, so the half-populated resume below
    # exercises real chunk granularity (same block size for every run:
    # chunk keys embed the exact index range).
    block_cells = max(POINTS_PER_WORKLOAD, out["points"] // 16)
    stale = ResultCache(root).study_dir(study) / "chunks"
    if stale.is_dir() and any(stale.iterdir()):
        raise SystemExit(
            f"error: {stale.parent} already holds chunks for this sweep — the "
            "benchmark measures a cold run; point --keep-cache at a fresh "
            "directory (or delete the old one)"
        )
    try:
        # 1. cold cached run
        cache = ResultCache(root, block_cells=block_cells)
        t0 = time.perf_counter()
        cold = study.run(cache=cache)
        out["cold_s"] = time.perf_counter() - t0
        out["points_per_s"] = out["points"] / out["cold_s"]
        out["chunks"] = cold.cache["misses"]
        assert cold.cache["hits"] == 0
        ref = np.asarray(cold.payload["optimal_tiers"], dtype=np.int64)

        # 2. kill half the chunks, resume: only the missing half recomputes
        files = sorted((cache.study_dir(study) / "chunks").glob("*.json"))
        for p in files[::2]:
            p.unlink()
        deleted = len(files[::2])
        t0 = time.perf_counter()
        resumed = study.run(cache=ResultCache(root, block_cells=block_cells))
        out["resume_s"] = time.perf_counter() - t0
        assert resumed.cache["misses"] == deleted, resumed.cache
        assert resumed.cache["hits"] == len(files) - deleted, resumed.cache
        assert np.array_equal(
            ref, np.asarray(resumed.payload["optimal_tiers"], dtype=np.int64)
        ), "resumed sweep diverged from the cold run"

        # 3. fully warm: nothing recomputes
        t0 = time.perf_counter()
        warm = study.run(cache=ResultCache(root, block_cells=block_cells))
        out["warm_s"] = time.perf_counter() - t0
        assert warm.cache["misses"] == 0 and warm.cache["hits"] == len(files)
        assert np.array_equal(
            ref, np.asarray(warm.payload["optimal_tiers"], dtype=np.int64)
        )
    finally:
        if not keep_cache:
            shutil.rmtree(root, ignore_errors=True)
    out["peak_rss_mb"] = _peak_rss_mb()
    out["match"] = True
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--points", type=int, default=1_000_000,
                    help="~design points in the sweep (workloads = points/48)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shard", default=None,
                    help="engine device-shard setting ('auto' | int); "
                         "switches the search to the jax backend")
    ap.add_argument("--keep-cache", default=None, metavar="DIR",
                    help="persist the chunk cache here (default: temp dir)")
    ap.add_argument("--smoke", action="store_true",
                    help="~20k-point sweep — the CI smoke step")
    args = ap.parse_args()
    out = run(20_000 if args.smoke else args.points, args.seed, args.shard,
              args.keep_cache)
    name = "BENCH_scale_smoke.json" if args.smoke else "BENCH_scale.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    print(f"{out['points']} points: cold {out['cold_s']:.1f}s "
          f"({out['points_per_s']:,.0f} points/s), resume {out['resume_s']:.1f}s, "
          f"warm {out['warm_s']:.2f}s, peak RSS {out['peak_rss_mb']:.0f} MB")


if __name__ == "__main__":
    use_compile_cache()
    main()
