"""Calibration benchmark: kernel speedups + fitted-model quality.

Pins three things into ``BENCH_calibrate.json``:

1. **Kernel speedups** — the current kernel wall times (via
   ``kernels_bench.bench_kernels``, median-of-reps) against the pinned
   pre-optimization timings (the ``PRE_OPT_US`` table below, recorded
   on this container before the fused-GQA / batched-GEMV /
   batched-SSM-scan / rectangular-block work landed).
2. **Fit quality** — a full ``kind='calibrate'`` study (default shape
   grid): fitted-model median relative error on held-out shapes next
   to the uncalibrated nominal-constants error.
3. **Artifact round-trip** — the fitted ``CalibratedBandwidth`` is
   saved to JSON, reloaded, fed to a ``kind='roofline'`` study via
   ``bandwidth=``, and the artifact of that study is required to be
   *bit-identical* to the same study run with the in-memory object.

Run:  PYTHONPATH=src python -m benchmarks.calibrate_bench [--smoke]
(``--smoke``: smoke-preset grid + single-rep kernel rows, same checks,
separate ``BENCH_calibrate_smoke.json`` — the CI step.)

Wall times are those of the device it runs on (the JSON names it); the
harness calibrates whatever backend it runs on.
"""

from __future__ import annotations

import argparse
import json
import pathlib

import jax

from repro.core.calibrate import CalibrateSpec
from repro.core.study import (
    AnalysisSpec,
    CalibratedBandwidth,
    Study,
    WorkloadSpec,
)
from repro._jax_compat import use_compile_cache

from .kernels_bench import bench_kernels

HERE = pathlib.Path(__file__).resolve().parent

#: median us per kernel row *before* this round of optimizations
#: (same shapes/reps as ``kernels_bench``, same container class).
PRE_OPT_US = {
    "kernels/dos_matmul_512x2048x512_bf16": 10055.46,
    "kernels/flash_chunked_1k_gqa": 39038.31,
    "kernels/flash_chunked_1k_bwd": 110089.68,
    "kernels/ssd_scan_1k_8h": 23307.15,
    "kernels/decode_attn_b8_4k_cache": 64082.75,
    "kernels/systolic_sim_16x96x16_l4": 356529.02,
}


def bench_speedups(reps: int = 3) -> list[dict]:
    rows = []
    for name, us, note, spread in bench_kernels(reps=reps):
        pre = PRE_OPT_US.get(name)
        rows.append({
            "name": name,
            "us": us,
            "spread_us": spread,
            "pre_opt_us": pre,
            "speedup_vs_pre_opt": (pre / us) if pre else None,
            "note": note,
        })
    return rows


def bench_calibration(smoke: bool) -> dict:
    spec = (
        CalibrateSpec(preset="smoke", reps=2, warmup=1)
        if smoke
        else CalibrateSpec(preset="default", reps=5, warmup=2)
    )
    study = Study(
        name="bench-calibrate",
        workload=WorkloadSpec(kind="gemms", gemms=((64, 64, 64),)),
        analysis=AnalysisSpec(kind="calibrate", calibrate=spec),
    )
    result = study.run()
    p = result.payload
    return {
        "preset": spec.preset,
        "errors": p["errors"],
        "dram_gbs_fitted": p["dram_gbs_fitted"],
        "efficiency": p["efficiency"],
        "artifact": p["artifact"].to_dict(),
    }


def bench_artifact_roundtrip(artifact_dict: dict) -> bool:
    """Reload the artifact from its JSON form, run the same roofline
    study with the reloaded and the original bandwidth, and require
    bit-identical result JSON."""
    art = CalibratedBandwidth.from_dict(json.loads(json.dumps(artifact_dict)))
    workload = WorkloadSpec(kind="gemms",
                            gemms=((64, 12100, 147), (512, 784, 128)))

    def roof(bw):
        return Study(
            name="bench-calibrate-roofline",
            workload=workload,
            analysis=AnalysisSpec(kind="roofline", bandwidth=bw),
        ).run().to_json()

    return roof(art) == roof(artifact_dict)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="smoke grid + single-rep kernel rows — the CI step")
    args = ap.parse_args()

    kernels = bench_speedups(reps=1 if args.smoke else 3)
    cal = bench_calibration(args.smoke)
    identical = bench_artifact_roundtrip(cal["artifact"])
    fast_rows = [
        r["name"] for r in kernels
        if r["speedup_vs_pre_opt"] and r["speedup_vs_pre_opt"] >= 1.3
    ]
    out = {
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "kernels": kernels,
        "n_rows_speedup_ge_1p3": len(fast_rows),
        "rows_speedup_ge_1p3": fast_rows,
        "calibration": cal,
        "artifact_roundtrip_bit_identical": identical,
    }
    name = "BENCH_calibrate_smoke.json" if args.smoke else "BENCH_calibrate.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    for r in kernels:
        s = (f"{r['speedup_vs_pre_opt']:.2f}x" if r["speedup_vs_pre_opt"]
             else "  -  ")
        print(f"{r['name']:<45} {r['us']:>12.1f} us  {s:>7} vs pre-opt")
    e = cal["errors"]
    u = e["uncalibrated_holdout_median_rel_err"]
    print(
        f"fit: holdout err {e['holdout_median_rel_err']:.1%} "
        f"(uncalibrated {'n/a: no published peak' if u is None else f'{u:.1%}'}); "
        f"roundtrip bit-identical: {identical}"
    )


ALL = [bench_speedups, bench_calibration]


if __name__ == "__main__":
    use_compile_cache()
    main()
