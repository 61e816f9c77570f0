"""Benchmark the Study facade against direct engine calls.

The declarative ``core.study.Study`` layer is the repo's one front
door; this benchmark proves the door is free. It times the same work
twice —

  - evaluate: Table-I workloads x (budget x tier) grid, all metric
    groups, ``engine.evaluate(grid)`` vs the equivalent
    ``Study(...).run()``;
  - schedule: one model-zoo cell lowered + scheduled,
    ``lower_network + engine.schedule`` vs the equivalent ``schedule``
    Study (which resolves the workload itself);

— asserts the results are bit-for-bit identical, and reports the
facade overhead, which must stay **< 5%** (min-of-reps timing; the
facade adds only spec validation and payload wrapping, no array
conversion). Writes ``BENCH_study.json`` next to this file.

Run:  PYTHONPATH=src python -m benchmarks.study_bench [--smoke] [--reps 5]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

from repro.configs import REGISTRY, SHAPES
from repro.core.dse import PAPER_WORKLOADS
from repro.core.engine import DesignGrid, evaluate, schedule
from repro.core.network import lower_network
from repro.core.study import AnalysisSpec, SpaceSpec, Study, WorkloadSpec
from repro._jax_compat import use_compile_cache

HERE = pathlib.Path(__file__).resolve().parent
OVERHEAD_TARGET_PCT = 5.0


def _paired(fn_a, fn_b, reps: int):
    """Time two implementations of the same work in alternating reps.

    Returns ``(out_a, out_b, best_a_s, best_b_s, overhead_pct)`` where
    ``overhead_pct`` is the **median of per-rep paired differences**
    (b - a) over the best a-time. Pairing cancels machine drift
    (frequency scaling, background load) that a min-over-independent-
    runs ratio picks up as fake +/- several percent; the median drops
    rep-level outliers (GC, interrupts)."""
    ta, tb = [], []
    out = [None, None]
    for _ in range(reps):
        for i, (fn, acc) in enumerate(((fn_a, ta), (fn_b, tb))):
            t0 = time.perf_counter()
            out[i] = fn()
            acc.append(time.perf_counter() - t0)
    diffs = np.asarray(tb) - np.asarray(ta)
    best_a = float(np.min(ta))
    overhead_pct = float(np.median(diffs)) / best_a * 100.0
    return out[0], out[1], best_a, float(np.min(tb)), overhead_pct


def bench_evaluate(reps: int, smoke: bool):
    wl = list(PAPER_WORKLOADS.values())
    budgets = (2**14, 2**16) if smoke else (2**14, 2**16, 2**18)
    tiers = tuple(range(1, 9)) if smoke else tuple(range(1, 17))

    def direct():
        return evaluate(DesignGrid.product(wl, budgets, tiers))

    study = Study(
        name="study-bench-evaluate",
        workload=WorkloadSpec(kind="gemms", gemms=wl),
        space=SpaceSpec(mac_budgets=budgets, tiers=tiers),
    )
    res_d, res_s, t_d, t_s, overhead = _paired(direct, lambda: study.run(), reps)
    for f in ("rows", "cols", "cycles", "speedup", "power_w", "t_max_c"):
        a, b = getattr(res_d, f), getattr(res_s.result, f)
        assert np.array_equal(a, b, equal_nan=True), f"evaluate mismatch in {f}"
    return {
        "grid": f"{len(wl)} workloads x {len(budgets) * len(tiers)} points",
        "direct_s": t_d,
        "study_s": t_s,
        "overhead_pct": overhead,
    }


def bench_schedule(reps: int, smoke: bool):
    # train_4k keeps the engine work in the hundreds of ms, so the
    # fixed facade cost (spec resolve + wrap) is measurable against it
    # rather than drowned in ms-scale timer jitter.
    arch, shape = "smollm-135m", "train_4k"
    # no reduced smoke grid here: the full cell is already ~0.2s, and a
    # smaller one would push the arms into ms-scale timer jitter where
    # the overhead ratio is meaningless.
    budgets = (2**14, 2**16, 2**18)
    tiers = tuple(range(1, 17))

    def direct():
        # the Study resolves its own workload, so the fair direct
        # baseline includes the lowering too
        stream = lower_network(REGISTRY[arch], SHAPES[shape])
        return schedule(stream, mac_budgets=budgets, tiers=tiers)

    study = Study(
        name="study-bench-schedule",
        workload=WorkloadSpec(kind="network", arch=arch, shape=shape),
        space=SpaceSpec(mac_budgets=budgets, tiers=tiers),
        analysis=AnalysisSpec(kind="schedule"),
    )
    rep_d, rep_s, t_d, t_s, overhead = _paired(direct, lambda: study.run(), reps)
    assert rep_d.to_dict() == rep_s.report.to_dict(), "schedule mismatch"
    return {
        "cell": f"{arch}/{shape}",
        "direct_s": t_d,
        "study_s": t_s,
        "overhead_pct": overhead,
    }


def run(smoke: bool = False, reps: int = 5):
    out = {
        "smoke": smoke,
        "reps": reps,
        "target_pct": OVERHEAD_TARGET_PCT,
        "evaluate": bench_evaluate(reps, smoke),
        "schedule": bench_schedule(reps, smoke),
        "match": True,
    }
    out["max_overhead_pct"] = max(
        out["evaluate"]["overhead_pct"], out["schedule"]["overhead_pct"]
    )
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grid (the CI smoke step)")
    ap.add_argument("--reps", type=int, default=5)
    args = ap.parse_args()
    out = run(smoke=args.smoke, reps=args.reps)
    # smoke runs get their own artifact so the canonical full-run
    # numbers are never clobbered
    name = "BENCH_study_smoke.json" if args.smoke else "BENCH_study.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    worst = out["max_overhead_pct"]
    print(f"facade overhead: {worst:.2f}% (target < {OVERHEAD_TARGET_PCT}%)")
    assert worst < OVERHEAD_TARGET_PCT, (
        f"Study facade overhead {worst:.2f}% exceeds {OVERHEAD_TARGET_PCT}%"
    )


if __name__ == "__main__":
    use_compile_cache()
    main()
