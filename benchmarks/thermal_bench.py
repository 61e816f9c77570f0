"""Transient thermal/DVFS benchmark: sustained vs peak, pinned flip.

Pins the ISSUE-9 acceptance story for the transient thermal model
(``core.ppa.thermal.ThermalState`` + ``core.pricing.DvfsSpec``):

1. **Steady-vs-transient agreement**: stepping the lumped RC stack
   under constant power converges to ``lumped_tier_temps``'s steady
   state — the fixed-point residual is reported and asserted below
   1e-9 relative (backward Euler shares the steady assembly, so the
   agreement is exact up to float64 roundoff).
2. **Sustained <= peak**: a governed serving run never reports more
   sustained tokens/s than the ungoverned steady pricing advertises
   as peak (asserted per design point).
3. **The feasibility flip**: under a junction limit between the 2D
   baseline's and the stacked design's *steady* temperatures, the
   3D point is steady-infeasible — the worst-case gate strikes it —
   yet transient-feasible: the governed excursion over the whole trace
   stays under the limit, and its sustained tokens/s beats the
   steady-feasible 2D baseline's. The steady model throws away the
   faster design; the transient model prices and keeps it.

Writes ``BENCH_thermal.json`` (or ``BENCH_thermal_smoke.json`` with
``--smoke``, the CI-sized run) next to this file.

Run:  PYTHONPATH=src python -m benchmarks.thermal_bench [--smoke]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import numpy as np

from repro.core.ppa.thermal import ThermalState, lumped_tier_temps, step_temps
from repro.core.study import (
    AnalysisSpec,
    BandwidthSpec,
    ConstraintSpec,
    ServeSpec,
    SpaceSpec,
    Study,
    TrafficSpec,
    WorkloadSpec,
)
from repro._jax_compat import use_compile_cache

HERE = pathlib.Path(__file__).resolve().parent

#: junction limit pinned between the steady temperatures of the 2D
#: baseline (68x240x1, ~54.1 degC) and the per-tier-budget-matched
#: stack (68x256x8, ~54.7 degC) of the study below.
FLIP_LIMIT_C = 54.4


def flip_study(smoke: bool = False, thermal: str = "steady") -> Study:
    """qwen2.5-3b decode serving on a per-tier-matched grid: the
    2**18-MAC 8-tier stack carries the same per-tier array as the
    2**14-MAC 2D die — the paper's Fig. 8 setup, where stacking the
    same tier is what concentrates the heat."""
    traffic = TrafficSpec(
        arrival_rps=2048.0,
        n_requests=8 if smoke else 24,
        prompt_dist="lognormal",
        prompt_mean=128,
        prompt_max=512,
        output_dist="lognormal",
        output_mean=24,
        output_max=96,
        sigma=0.6,
        max_batch=4,
        policy="continuous",
        chunk_prefill=64,
        seed=0,
    )
    return Study(
        name=f"bench-thermal-{thermal}" + ("-smoke" if smoke else ""),
        workload=WorkloadSpec(kind="network", arch="qwen2.5-3b",
                              shape="decode_32k"),
        space=SpaceSpec(mac_budgets=(2**14, 2**18), tiers=(1, 8)),
        constraints=ConstraintSpec(thermal_limit_c=FLIP_LIMIT_C),
        analysis=AnalysisSpec(
            kind="serve",
            thermal=thermal,
            bandwidth=BandwidthSpec.paper_default(),
            serve=ServeSpec(traffic=traffic),
        ),
    )


def fixed_point_residual() -> dict:
    """Step the RC stack under constant power until the transient
    temperatures converge; compare against the one-shot steady solve."""
    fp = np.array([4.2, 4.2, 30.0])
    tiers = np.array([4, 8, 1])
    tech = np.array(["tsv", "miv", "2d"])
    macs = np.array([4096.0, 4096.0, 65536.0])
    q_tier = np.array([1.5, 0.8, 6.0])
    q = np.where(
        np.arange(tiers.max())[None, :] < tiers[:, None],
        q_tier[:, None], 0.0,
    )
    steady = lumped_tier_temps(q, fp, tiers, tech, macs)
    state = ThermalState.init(fp, tiers, tech, macs)
    t0 = time.perf_counter()
    n_steps = 400
    for _ in range(n_steps):
        state = step_temps(state, q, np.full(3, 0.05))
    elapsed = time.perf_counter() - t0
    alive = state.alive
    rel = np.abs(state.temps_c - steady)[alive] / np.abs(steady[alive])
    return {
        "n_steps": n_steps,
        "dt_s": 0.05,
        "step_s": elapsed / n_steps,
        "max_rel_err": float(rel.max()),
    }


def _point_rows(p: dict) -> list[dict]:
    pts = p["points"]
    return [
        {
            "design": f"{pts['rows'][i]}x{pts['cols'][i]}x{pts['tiers'][i]}",
            "tech": str(pts["tech"][i]),
            "feasible_steady": bool(pts["feasible_steady"][i]),
            "feasible_transient": bool(pts["feasible"][i]),
            "t_max_steady_c": float(pts["t_max_c"][i]),
            "t_max_governed_c": float(pts["t_max_transient_c"][i]),
            "peak_tok_s": float(pts["peak_tok_s"][i]),
            "sustained_tok_s": float(pts["gen_tok_s"][i]),
            "peak_vs_sustained": float(pts["peak_vs_sustained"][i]),
            "residency": [float(x) for x in pts["dvfs_residency"][i]],
        }
        for i in range(p["n_points"])
    ]


def run(smoke: bool = False) -> dict:
    out: dict = {"thermal_limit_c": FLIP_LIMIT_C}

    # 1. transient stepping agrees with the steady solver
    out["fixed_point"] = fixed_point_residual()
    assert out["fixed_point"]["max_rel_err"] < 1e-9, out["fixed_point"]

    # 2+3. steady gate vs governed transient on the same grid
    steady = flip_study(smoke, "steady").run()
    t0 = time.perf_counter()
    trans = flip_study(smoke, "transient").run()
    out["transient_s"] = time.perf_counter() - t0
    p = trans.payload
    pts = p["points"]
    out["dvfs"] = p["dvfs"]
    out["points"] = _point_rows(p)

    # the steady study's verdicts match the transient study's
    # feasible_steady column (same designs, same gate)
    assert (steady.payload["points"]["feasible"] == pts["feasible_steady"]).all()

    # sustained never exceeds peak; residency is a distribution
    ok = pts["valid"]
    assert (pts["peak_vs_sustained"][ok] >= 1.0 - 1e-12).all()
    assert np.allclose(pts["dvfs_residency"][ok].sum(axis=1), 1.0)
    # governed excursion under the limit wherever transient-feasible
    feas = pts["feasible"]
    assert (pts["t_max_transient_c"][feas] < FLIP_LIMIT_C).all()

    # the pinned flip: a 3D point the steady gate strikes, serving
    # faster than the steady-feasible 2D baseline under the governor
    flip = feas & ~pts["feasible_steady"] & (pts["tiers"] > 1)
    assert flip.any(), "no steady-infeasible 3D point became feasible"
    base2d = pts["feasible_steady"] & (pts["tiers"] == 1)
    assert base2d.any(), "no steady-feasible 2D baseline"
    i3 = int(np.argmax(np.where(flip, pts["gen_tok_s"], -np.inf)))
    i2 = int(np.argmax(np.where(base2d, pts["gen_tok_s"], -np.inf)))
    win = float(pts["gen_tok_s"][i3] / pts["gen_tok_s"][i2])
    out["flip"] = {
        "design_3d": f"{pts['rows'][i3]}x{pts['cols'][i3]}x{pts['tiers'][i3]}",
        "design_2d": f"{pts['rows'][i2]}x{pts['cols'][i2]}x{pts['tiers'][i2]}",
        "t_steady_3d_c": float(pts["t_max_c"][i3]),
        "t_governed_3d_c": float(pts["t_max_transient_c"][i3]),
        "sustained_3d_tok_s": float(pts["gen_tok_s"][i3]),
        "sustained_2d_tok_s": float(pts["gen_tok_s"][i2]),
        "win_3d_vs_2d_sustained": win,
    }
    assert pts["t_max_c"][i3] > FLIP_LIMIT_C  # steady gate really struck it
    assert win > 1.0, f"throttled 3D does not beat 2D sustained: {win}"

    out["study"] = trans.study.name
    out["arch"] = p["arch"]
    out["n_points"] = p["n_points"]
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace — BENCH_thermal_smoke.json")
    args = ap.parse_args()
    out = run(smoke=args.smoke)
    name = "BENCH_thermal_smoke.json" if args.smoke else "BENCH_thermal.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    f = out["flip"]
    print(
        f"{out['arch']}: steady gate at {out['thermal_limit_c']} degC strikes "
        f"{f['design_3d']} (steady {f['t_steady_3d_c']:.1f} degC); governed it "
        f"stays at {f['t_governed_3d_c']:.1f} degC and sustains "
        f"{f['sustained_3d_tok_s']:.0f} tok/s vs the 2D baseline's "
        f"{f['sustained_2d_tok_s']:.0f} ({f['win_3d_vs_2d_sustained']:.2f}x); "
        f"fixed-point residual {out['fixed_point']['max_rel_err']:.1e}"
    )


if __name__ == "__main__":
    use_compile_cache()
    main()
