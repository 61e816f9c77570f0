"""Kernel microbenchmarks (CPU jnp paths; Pallas validated separately).

Times the layer-facing ops that the models hot-path through, plus the
cycle-level systolic simulator. Wall times here are CPU numbers — the
TPU story lives in the roofline benchmark — but they track relative
regressions and prove the ops run.

Run:  PYTHONPATH=src python -m benchmarks.kernels_bench [--smoke]
writes ``BENCH_kernels.json`` (``BENCH_kernels_smoke.json`` with
``--smoke``: single-rep timings, same ops) next to this file.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.systolic import simulate_dos_3d
from repro.kernels.dos_matmul import dos_matmul
from repro.kernels.flash_attention import decode_attention
from repro.kernels.flash_attention.ops import flash_attention_jnp
from repro.kernels.ssm_scan import ssm_scan
from repro._jax_compat import use_compile_cache

HERE = pathlib.Path(__file__).resolve().parent


def _timeit(fn, *args, reps=3, warmup=2):
    """Median-of-reps wall time in us, plus dispersion (max - min).

    Each rep is individually timed after ``warmup`` untimed calls; the
    median is robust to the scheduler hiccups that a mean-of-3 on a
    1-CPU CI box folds straight into the pin.
    """
    for _ in range(max(warmup, 1)):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts)), float(max(ts) - min(ts))


def bench_kernels(reps: int = 3):
    rng = np.random.default_rng(0)
    rows = []

    a = jnp.asarray(rng.normal(size=(512, 2048)), jnp.bfloat16)
    b = jnp.asarray(rng.normal(size=(2048, 512)), jnp.bfloat16)
    f = jax.jit(lambda a, b: dos_matmul(a, b))
    us, spread = _timeit(f, a, b, reps=reps)
    gf = 2 * 512 * 2048 * 512 / (us / 1e6) / 1e9
    rows.append(("kernels/dos_matmul_512x2048x512_bf16", us, f"{gf:.1f} GFLOP/s cpu", spread))

    q = jnp.asarray(rng.normal(size=(1, 1024, 8, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1024, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 1024, 2, 64)), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention_jnp(q, k, v, causal=True))
    us, spread = _timeit(f, q, k, v, reps=reps)
    rows.append(("kernels/flash_chunked_1k_gqa", us, "fwd, fused GQA", spread))

    f = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention_jnp(q, k, v) ** 2)))
    us, spread = _timeit(f, q, k, v, reps=reps)
    rows.append(("kernels/flash_chunked_1k_bwd", us, "custom-vjp", spread))

    u = jnp.asarray(rng.normal(size=(2, 1024, 8, 64)), jnp.float32)
    ld = jnp.asarray(-rng.uniform(0.01, 0.2, size=(2, 1024, 8)), jnp.float32)
    B = jnp.asarray(rng.normal(size=(2, 1024, 8, 64)), jnp.float32)
    C = jnp.asarray(rng.normal(size=(2, 1024, 8, 64)), jnp.float32)
    f = jax.jit(lambda *x: ssm_scan(*x)[0])
    us, spread = _timeit(f, u, ld, B, C, reps=reps)
    rows.append(("kernels/ssd_scan_1k_8h", us, "chunk=auto (32 on cpu)", spread))

    qd = jnp.asarray(rng.normal(size=(8, 1, 16, 64)), jnp.float32)
    kc = jnp.asarray(rng.normal(size=(8, 4096, 4, 64)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(8, 4096, 4, 64)), jnp.float32)
    f = jax.jit(lambda q, k, v: decode_attention(q, k, v, length=4000))
    us, spread = _timeit(f, qd, kc, vc, reps=reps)
    rows.append(("kernels/decode_attn_b8_4k_cache", us, "batched-GEMV path", spread))

    A = jnp.asarray(rng.normal(size=(16, 96)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(96, 16)), jnp.float32)
    # cold time on purpose: this row tracks trace+compile+run of the
    # cycle simulator, which is how Study sweeps hit it (once per shape).
    t0 = time.perf_counter()
    r = simulate_dos_3d(A, Bm, 8, 8, 4)
    us = (time.perf_counter() - t0) * 1e6
    rows.append(("kernels/systolic_sim_16x96x16_l4", us, f"{r.cycles} cycles (cold)", 0.0))
    return rows


ALL = [bench_kernels]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="single-rep timings — the CI smoke step")
    args = ap.parse_args()
    rows = bench_kernels(reps=1 if args.smoke else 3)
    out = {
        "smoke": args.smoke,
        "backend": jax.default_backend(),
        "rows": [
            {"name": n, "us": us, "note": note, "spread_us": spread}
            for n, us, note, spread in rows
        ],
    }
    name = "BENCH_kernels_smoke.json" if args.smoke else "BENCH_kernels.json"
    (HERE / name).write_text(json.dumps(out, indent=1))
    print(json.dumps(out, indent=1))
    for n, us, note, spread in rows:
        print(f"{n:<45} {us:>12.1f} us (±{spread:.0f})  {note}")


if __name__ == "__main__":
    use_compile_cache()
    main()
