"""Serving-traffic benchmark: sustained 3D-vs-2D on a mixed trace.

Pins the production-serving story of ``core.serve`` (the ISSUE-8
acceptance artifact): a seeded mixed prefill/decode trace on a zoo
model, priced per design point through the bandwidth-aware engine under
the paper-default memory system, where

1. a **feasible 3D design beats the 2D baseline on tokens/s/W** (the
   single-tier die must over-provision one big array that stalls on
   DRAM and burns static power; the stack spends the same MAC budget at
   a higher sustained efficiency) — asserted, with p50/p99 TTFT and
   per-output-token latency reported per point;
2. a **half-populated cache resumes bit-identically**: delete half the
   per-point chunk files, re-run via ``--resume`` semantics, assert
   exactly the missing design points recompute and the stitched payload
   matches the cold run bit for bit (then a warm run recomputes
   nothing).

Writes ``BENCH_serve.json`` (or ``BENCH_serve_smoke.json`` with
``--smoke``, the CI-sized run) next to this file.

Run:  PYTHONPATH=src python -m benchmarks.serve_bench [--smoke]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import tempfile
import time

import numpy as np

from repro.core.cache import ResultCache
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


def serve_study(smoke: bool = False) -> Study:
    """The pinned serving study: qwen2.5-3b decode under the
    paper-default memory system. Budget-matched tier counts 1..8 — the
    2D baseline is the tiers=1 column of the same grid."""
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
        name="bench-serve-smoke" if smoke else "bench-serve",
        workload=WorkloadSpec(kind="network", arch="qwen2.5-3b",
                              shape="decode_32k"),
        space=SpaceSpec(
            mac_budgets=(2**16,) if smoke else (2**14, 2**16, 2**18),
            tiers=(1, 2, 4) if smoke else (1, 2, 4, 8),
        ),
        constraints=ConstraintSpec(),
        analysis=AnalysisSpec(
            kind="serve",
            bandwidth=BandwidthSpec.paper_default(),
            serve=ServeSpec(traffic=traffic),
        ),
    )


def _point_rows(p: dict) -> list[dict]:
    pts = p["points"]
    return [
        {
            "design": f"{pts['rows'][i]}x{pts['cols'][i]}x{pts['tiers'][i]}",
            "tech": str(pts["tech"][i]),
            "feasible": bool(pts["feasible"][i]),
            "gen_tok_s": float(pts["gen_tok_s"][i]),
            "ttft_p50_s": float(pts["ttft_p50_s"][i]),
            "ttft_p99_s": float(pts["ttft_p99_s"][i]),
            "tpot_p50_s": float(pts["tpot_p50_s"][i]),
            "tpot_p99_s": float(pts["tpot_p99_s"][i]),
            "energy_per_token_j": float(pts["energy_per_token_j"][i]),
            "tokens_per_s_per_w": float(pts["tokens_per_s_per_w"][i]),
            "stall_frac": float(pts["stall_frac"][i]),
        }
        for i in range(p["n_points"])
    ]


def run(smoke: bool = False, keep_cache: str | None = None) -> dict:
    study = serve_study(smoke)
    tr = study.analysis.serve.traffic
    root = pathlib.Path(keep_cache) if keep_cache else pathlib.Path(
        tempfile.mkdtemp(prefix="repro-serve-")
    )
    # one design point per chunk, so the half-populated resume below
    # exercises per-point granularity (chunk keys embed the index range)
    block_cells = tr.n_requests
    out: dict = {}
    try:
        # 1. cold cached run
        cache = ResultCache(root, block_cells=block_cells)
        t0 = time.perf_counter()
        cold = study.run(cache=cache)
        out["cold_s"] = time.perf_counter() - t0
        assert cold.cache["hits"] == 0
        p = cold.payload
        ref_json = json.dumps(cold.to_dict()["payload"], sort_keys=True)

        s = p["summary"]
        assert s["best_3d"] is not None, "no feasible 3D design"
        assert s["best_2d"] is not None, "no feasible 2D design"
        assert s["win_3d_vs_2d"] > 1.0, (
            f"3D does not beat 2D on tokens/s/W: {s['win_3d_vs_2d']}"
        )
        pts = p["points"]
        assert np.isfinite(pts["ttft_p50_s"]).all()
        assert np.isfinite(pts["ttft_p99_s"]).all()
        assert np.isfinite(pts["tpot_p50_s"]).all()
        # conservation: every admitted token was served
        assert int(pts["tokens_prefilled"][0]) == p["trace"]["tokens_in"]
        assert int(pts["tokens_decoded"][0]) == p["trace"]["tokens_out"]

        # 2. kill half the chunks, resume: exactly the missing design
        # points recompute; stitched payload is bit-identical
        files = sorted((cache.study_dir(study) / "chunks").glob("points-*.json"))
        out["chunks"] = len(files)
        for f in files[::2]:
            f.unlink()
        deleted = len(files[::2])
        t0 = time.perf_counter()
        resumed = study.run(cache=ResultCache(root, block_cells=block_cells))
        out["resume_s"] = time.perf_counter() - t0
        assert resumed.cache["misses"] == deleted, resumed.cache
        assert resumed.cache["hits"] == len(files) - deleted, resumed.cache
        assert json.dumps(resumed.to_dict()["payload"], sort_keys=True) == ref_json, (
            "resumed serve payload diverged from the cold run"
        )

        # 3. fully warm: nothing recomputes
        warm = study.run(cache=ResultCache(root, block_cells=block_cells))
        assert warm.cache["misses"] == 0 and warm.cache["hits"] == len(files)
        assert json.dumps(warm.to_dict()["payload"], sort_keys=True) == ref_json
    finally:
        if not keep_cache:
            shutil.rmtree(root, ignore_errors=True)

    out.update({
        "study": study.name,
        "arch": p["arch"],
        "shape": p["shape"],
        "n_points": p["n_points"],
        "trace": p["trace"],
        "traffic": tr.to_dict(),
        "points": _point_rows(p),
        "summary": s,
        "resume_bit_identical": True,
    })
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized trace/grid — BENCH_serve_smoke.json")
    ap.add_argument("--keep-cache", default=None, metavar="DIR",
                    help="persist the chunk cache here (default: temp dir)")
    args = ap.parse_args()
    out = run(smoke=args.smoke, keep_cache=args.keep_cache)
    name = "BENCH_serve_smoke.json" if args.smoke else "BENCH_serve.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    s = out["summary"]
    print(
        f"{out['arch']}/{out['shape']}: {out['n_points']} design points, "
        f"best 3D {s['best_3d']['tokens_per_s_per_w']:.1f} tok/s/W vs 2D "
        f"{s['best_2d']['tokens_per_s_per_w']:.1f} ({s['win_3d_vs_2d']:.2f}x); "
        f"cold {out['cold_s']:.2f}s, resume {out['resume_s']:.2f}s"
    )


if __name__ == "__main__":
    use_compile_cache()
    main()
