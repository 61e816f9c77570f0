"""Benchmark the network-level mapping path (zoo -> lowering -> schedule).

Lowers every live (arch, shape) cell of the model zoo to its GEMM
stream and schedules it end-to-end — each cell is one declarative
``schedule`` Study (``core.study``) compiled into
``core.engine.schedule`` — timing the lowering and the scheduling
separately. Sanity checks ride along: every stream is non-empty, every
report is finite, and the fixed-design policy is never faster than
per-layer-optimal.

Writes ``BENCH_network.json`` next to this file.

Run:  PYTHONPATH=src python -m benchmarks.network_bench [--smoke] [--jax]
``--smoke`` runs a 2-arch x 2-shape subset on a reduced grid — the CI
regression-visibility step.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

from repro.core.network import lower_zoo
from repro.core.study import AnalysisSpec, SpaceSpec, Study, WorkloadSpec
from repro._jax_compat import use_compile_cache

HERE = pathlib.Path(__file__).resolve().parent

SMOKE_ARCHS = ("smollm-135m", "deepseek-moe-16b")
SMOKE_SHAPES = ("train_4k", "decode_32k")


def run(smoke: bool = False, backend: str = "numpy"):
    from repro.configs import cells as zoo_cells

    space = SpaceSpec()
    archs = shapes = None
    if smoke:
        archs, shapes = set(SMOKE_ARCHS), set(SMOKE_SHAPES)
        space = SpaceSpec(mac_budgets=(2**14, 2**16), tiers=tuple(range(1, 9)))
    # lowering-only timing (the Study runs below re-lower their own
    # cell as part of workload resolution; that cost — ~0.5 ms/cell vs
    # ~0.7 s of scheduling — rides inside schedule_s)
    t0 = time.perf_counter()
    lower_zoo(shapes=shapes, archs=archs)
    lower_s = time.perf_counter() - t0

    live, _ = zoo_cells()
    cells = []
    t0 = time.perf_counter()
    for arch, shape in live:
        if archs is not None and arch not in archs:
            continue
        if shapes is not None and shape not in shapes:
            continue
        rep = Study(
            workload=WorkloadSpec(kind="network", arch=arch, shape=shape),
            space=space,
            analysis=AnalysisSpec(kind="schedule", backend=backend),
        ).run().report
        pl, fx = rep.per_layer, rep.fixed
        assert rep.n_gemms > 0, (arch, shape)
        assert np.isfinite(pl.total_cycles) and np.isfinite(fx.total_cycles), (
            arch, shape)
        assert fx.total_cycles >= pl.total_cycles, (arch, shape)
        cells.append({
            "arch": rep.arch, "shape": rep.shape, "mode": rep.mode,
            "n_gemms": rep.n_gemms,
            "n_gemm_invocations": rep.n_gemm_invocations,
            "total_macs": rep.total_macs,
            "per_layer_cycles": pl.total_cycles,
            "fixed_cycles": fx.total_cycles,
            "fixed_over_opt": fx.total_cycles / pl.total_cycles,
            "fixed_speedup_vs_2d": fx.speedup_vs_2d,
            "fixed_energy_j": fx.energy_j,
            "fixed_edp_js": fx.edp_js,
            "fixed_t_max_c": fx.t_max_c,
            "fixed_design_rcl": [int(x) for x in np.asarray(fx.design)],
            "n_candidates": rep.n_candidates,
            "n_thermally_masked": rep.n_thermally_masked,
        })
    sched_s = time.perf_counter() - t0

    points = sum(c["n_gemms"] * c["n_candidates"] for c in cells)
    return {
        "smoke": smoke,
        "backend": backend,
        "n_cells": len(cells),
        "design_points_evaluated": points,
        "lower_s": lower_s,
        "schedule_s": sched_s,
        "points_per_s": points / sched_s if sched_s else float("nan"),
        "all_fixed_ge_per_layer": True,
        "cells": cells,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small subset + reduced grid (CI smoke step)")
    ap.add_argument("--jax", action="store_true",
                    help="use the jitted JAX search backend")
    args = ap.parse_args()
    t0 = time.perf_counter()
    out = run(smoke=args.smoke, backend="jax" if args.jax else "numpy")
    out["total_s"] = time.perf_counter() - t0
    # smoke runs get their own artifact so the canonical full-sweep
    # numbers (committed + uploaded by CI) are never clobbered
    name = "BENCH_network_smoke.json" if args.smoke else "BENCH_network.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps({k: v for k, v in out.items() if k != "cells"}, indent=1))
    worst = max(out["cells"], key=lambda c: c["fixed_over_opt"])
    print(f"worst fixed/per-layer gap: {worst['fixed_over_opt']:.3f}x "
          f"({worst['arch']}/{worst['shape']})")


if __name__ == "__main__":
    use_compile_cache()
    main()
