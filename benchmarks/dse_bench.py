"""Benchmark the batched DSE engine against the legacy per-point loop.

Times the full Fig-7-style sweep — N random workloads x 3 MAC budgets x
16 tier counts, each point requiring a full (R, C) shape search — two
ways:

  - legacy: the pre-engine per-point Python loop (scalar
    ``analytical.optimal_tiers`` per workload x budget), and
  - engine: one declarative Fig-7 Study (``core.dse.fig7_study``) whose
    ``run()`` is a single ``optimal_tiers_batched`` engine call
    (optionally with the jitted JAX search backend).

Asserts both agree exactly, prints the speedup, and writes
``BENCH_dse.json`` next to this file.

Run:  PYTHONPATH=src python -m benchmarks.dse_bench [--n 300] [--jax]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

from repro.core.analytical import optimal_tiers
from repro.core.dse import fig7_study, random_workloads
from repro.core.engine import optimal_tiers_batched
from repro._jax_compat import use_compile_cache

HERE = pathlib.Path(__file__).resolve().parent
BUDGETS = (2**14, 2**16, 2**18)
MAX_TIERS = 16


def run(n_workloads: int = 300, seed: int = 0, jax_backend: bool = False):
    wl = random_workloads(n_workloads, seed)

    t0 = time.perf_counter()
    legacy = np.array(
        [
            [optimal_tiers(m, k, n, b, MAX_TIERS)[0] for b in BUDGETS]
            for m, k, n in wl
        ]
    )
    legacy_s = time.perf_counter() - t0

    backends = ["numpy"] + (["jax"] if jax_backend else [])
    out = {
        "sweep": f"{n_workloads} workloads x {len(BUDGETS)} budgets x {MAX_TIERS} tiers",
        "points": n_workloads * len(BUDGETS) * MAX_TIERS,
        "legacy_s": legacy_s,
    }
    for backend in backends:
        if backend == "jax":  # warm the jit cache outside the timed region
            optimal_tiers_batched(wl[:8], BUDGETS, MAX_TIERS, backend="jax")
        study = fig7_study(BUDGETS, n_workloads, seed, MAX_TIERS, backend=backend)
        t0 = time.perf_counter()
        res = study.run()
        dt = time.perf_counter() - t0
        best = np.asarray(res.payload["optimal_tiers"], dtype=np.int64)
        assert np.array_equal(best, legacy), "engine disagrees with legacy loop"
        out[f"engine_{backend}_s"] = dt
        out[f"speedup_{backend}"] = legacy_s / dt
    out["match"] = True
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=300, help="number of workloads")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--jax", action="store_true", help="also time the JAX backend")
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep (40 workloads) — the CI smoke step")
    args = ap.parse_args()
    out = run(40 if args.smoke else args.n, args.seed, args.jax)
    # smoke runs get their own artifact so the canonical full-sweep
    # numbers (committed + uploaded by CI) are never clobbered
    name = "BENCH_dse_smoke.json" if args.smoke else "BENCH_dse.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    for k in out:
        if k.startswith("speedup"):
            print(f"{k}: {out[k]:.1f}x  (target >= 10x)")


if __name__ == "__main__":
    use_compile_cache()
    main()
