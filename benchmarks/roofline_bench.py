"""Benchmark the engine-backed bandwidth/roofline model (§Roofline).

Runs one declarative ``roofline`` Study — N random Fig-7-style
workloads x 3 MAC budgets x 16 tier counts under
``BandwidthSpec.paper_default()`` — and checks it against two
independent references:

  - scalar identity: for a sample of design points, the batched
    ``gemm_traffic_batched`` + ``roofline_cycles`` pipeline is
    recomputed point-by-point (batch of one) and must agree exactly;
  - uncapped identity: the same study with an unbounded spec must be
    bit-for-bit equal to the plain compute-bound ``evaluate`` — the
    contract that keeps every pre-bandwidth result valid.

Prints the points/s throughput and bound histogram, and writes
``BENCH_roofline.json`` next to this file. The TPU dry-run artifact
table this benchmark used to print now lives in
``experiments/make_report.py`` (``python -m repro report``).

Run:  PYTHONPATH=src python -m benchmarks.roofline_bench [--n 300] [--smoke]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

from repro.core.bandwidth import BandwidthSpec, gemm_traffic_batched, roofline_cycles
from repro.core.dse import random_workloads
from repro.core.engine import DesignGrid, evaluate
from repro.core.study import AnalysisSpec, SpaceSpec, Study, WorkloadSpec
from repro._jax_compat import use_compile_cache

HERE = pathlib.Path(__file__).resolve().parent
BUDGETS = (2**14, 2**16, 2**18)
MAX_TIERS = 16


def _scalar_check(res, grid, spec: BandwidthSpec, n_sample: int = 64) -> None:
    """Recompute a sample of points one at a time; must match exactly."""
    rng = np.random.default_rng(0)
    W, P = res.valid.shape
    for _ in range(n_sample):
        w, p = int(rng.integers(W)), int(rng.integers(P))
        if not res.valid[w, p]:
            continue
        M, K, N = (int(x) for x in grid.workloads[w])
        tr = gemm_traffic_batched(
            "dos", [M], [K], [N], [int(res.rows[w, p])], [int(res.cols[w, p])],
            [int(grid.tiers[p])], np.asarray(["tsv"]), spec,
        )
        assert tr["dram_bytes"][0] == res.dram_bytes[w, p], (w, p)
        compute = res.cycles[w, p] - res.stall_cycles[w, p]
        total, stall, _ = roofline_cycles(
            [compute], tr["dram_bytes"] / spec.dram_bytes_per_cycle,
            tr["vlink_cycles"],
        )
        assert total[0] == res.cycles[w, p], (w, p)
        assert stall[0] == res.stall_cycles[w, p], (w, p)


def vlink_scenario():
    """A sweep where the vertical-link bound actually binds.

    The headline sweep's Fig-7-style workloads have K large enough that
    every fold carries ~``ceil(K/L)`` compute cycles against ~15 cycles
    of shared-TSV partial-sum drain, so ``bound_counts.vlink`` stays 0
    there. Short-contraction (decode-like) GEMMs under tiny MAC budgets
    at high tier counts flip that: the array comes out narrow, each
    fold carries just a few MAC cycles, and the shared TSV bus drains
    partial sums slower than the pile makes them. This study pins that
    regime — the row asserts ``vlink > 0``.
    """
    study = Study(
        name="roofline-bench-vlink",
        workload=WorkloadSpec(
            kind="gemms",
            gemms=((64, 8, 64), (128, 16, 128), (256, 32, 256)),
        ),
        space=SpaceSpec(
            mac_budgets=(64, 256),
            tiers=(8, 16),
            dataflow=("dos",),
            tech=("tsv",),
        ),
        analysis=AnalysisSpec(kind="roofline", bandwidth=BandwidthSpec.paper_default()),
    )
    out = study.run()
    counts = out.payload["bound_counts"]
    assert counts["vlink"] > 0, f"vlink never binds: {counts}"
    return {
        "sweep": "3 short-K gemms x budgets (64,256) x tiers (8,16), dos/tsv",
        "points": int(np.sum(out.result.valid)),
        "bound_counts": counts,
        "stall_frac": out.payload["stall_frac"],
    }


def fold_scenario():
    """The vlink technology decides the best intra-layer fold.

    ``(M, K, N) = (12, 7000, 12)`` on a 4x4 array across 3 tiers: the
    contraction is deep but the array is tiny, so folding the output
    rows (fold-m) saves ~0.4% of compute cycles over the native fold-K
    — but only if the L-1 partial-sum planes it creates drain fast
    enough. MIVs (17 bits/MAC) swallow them; the shared TSV bus
    (17/16 bits/MAC) turns the same mapping vlink-bound at ~1.9x the
    cycles. One workload, one array — two technologies, two best
    folds. The row asserts the flip so the regression is pinned here
    as well as in ``tests/test_bandwidth.py``.
    """
    from repro.core.pricing import price_steps

    spec = BandwidthSpec.paper_default()
    out = {}
    for tech in ("tsv", "miv"):
        cyc = {}
        for fold in (None, "m"):
            pr = price_steps(
                "os", np.array([12]), np.array([7000]), np.array([12]),
                np.array([4]), np.array([4]), np.array([3]),
                np.array([tech]), spec, fold=fold,
            )
            cyc["native_k" if fold is None else "fold_m"] = float(
                pr["total_cycles"][0])
        out[tech] = cyc
    assert out["miv"]["fold_m"] < out["miv"]["native_k"], out
    assert out["tsv"]["fold_m"] > out["tsv"]["native_k"], out
    return {
        "workload": [12, 7000, 12],
        "design": "os, 4x4 array, 3 tiers, paper-default memory",
        "cycles": out,
        "flip": "miv -> fold_m wins; tsv -> native fold-k wins",
    }


def run(n_workloads: int = 300, seed: int = 0):
    spec = BandwidthSpec.paper_default()
    study = Study(
        name=f"roofline-bench-{n_workloads}",
        workload=WorkloadSpec(kind="random", n=n_workloads, seed=seed),
        space=SpaceSpec(mac_budgets=BUDGETS, tiers=tuple(range(1, MAX_TIERS + 1))),
        analysis=AnalysisSpec(kind="roofline", bandwidth=spec),
    )
    t0 = time.perf_counter()
    out_study = study.run()
    bw_s = time.perf_counter() - t0
    res = out_study.result
    grid = res.grid

    _scalar_check(res, grid, spec)

    # Uncapped bit-identity vs the plain compute-bound evaluate.
    wl = random_workloads(n_workloads, seed)
    plain = evaluate(DesignGrid.product(wl, BUDGETS, range(1, MAX_TIERS + 1)))
    unb = evaluate(
        DesignGrid.product(wl, BUDGETS, range(1, MAX_TIERS + 1)),
        bandwidth=BandwidthSpec(),
    )
    assert np.array_equal(plain.cycles, unb.cycles)
    assert np.array_equal(plain.speedup, unb.speedup, equal_nan=True)
    assert float(np.nansum(unb.stall_cycles)) == 0.0

    points = n_workloads * len(BUDGETS) * MAX_TIERS
    return {
        "sweep": f"{n_workloads} workloads x {len(BUDGETS)} budgets x {MAX_TIERS} tiers",
        "points": points,
        "bandwidth": spec.to_dict(),
        "roofline_s": bw_s,
        "points_per_s": points / bw_s,
        "bound_counts": out_study.payload["bound_counts"],
        "stall_frac": out_study.payload["stall_frac"],
        "speedup_max_compute": float(np.nanmax(plain.speedup)),
        "speedup_max_bw": float(np.nanmax(res.speedup)),
        "scalar_match": True,
        "uncapped_identity": True,
        "vlink_scenario": vlink_scenario(),
        "fold_scenario": fold_scenario(),
    }


def bench_roofline():
    """benchmarks.run entry: small engine-backed roofline summary rows."""
    out = run(40)
    us = out["roofline_s"] * 1e6
    vl = out["vlink_scenario"]
    return [
        ("roofline/engine_sweep", us,
         f"{out['points']} pts; bounds {out['bound_counts']}; "
         f"stall {out['stall_frac']:.2f}"),
        ("roofline/speedup_collapse", 0.0,
         f"compute-bound {out['speedup_max_compute']:.2f}x -> "
         f"bw-aware {out['speedup_max_bw']:.2f}x"),
        ("roofline/vlink_binds", 0.0,
         f"short-K dos/tsv: bounds {vl['bound_counts']}"),
        ("roofline/fold_flip", 0.0, out["fold_scenario"]["flip"]),
    ]


ALL = [bench_roofline]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=300, help="number of workloads")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="small sweep (40 workloads) — the CI smoke step")
    args = ap.parse_args()
    out = run(40 if args.smoke else args.n, args.seed)
    name = "BENCH_roofline_smoke.json" if args.smoke else "BENCH_roofline.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    print(f"points/s: {out['points_per_s']:.0f}  "
          f"speedup collapse: {out['speedup_max_compute']:.2f}x -> "
          f"{out['speedup_max_bw']:.2f}x")


if __name__ == "__main__":
    use_compile_cache()
    main()
