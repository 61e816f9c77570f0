"""Serving launcher: batched prefill + decode loop.

Drives the same prefill/serve steps the dry-run lowers, on real
devices. Measures prefill latency, aggregate decode throughput and
per-token decode latency percentiles (each step synchronized, so the
median/p99 spread is visible, not averaged away); the examples use it
with reduced configs and ``--json`` emits the machine-readable summary
CI smoke checks parse.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, reduced
from ..models import build
from ..parallel.axes import ShardingRules, param_sharding, use_rules
from .mesh import make_test_mesh


def serve_loop(
    cfg,
    *,
    batch: int = 4,
    prompt_len: int = 64,
    gen_tokens: int = 32,
    strategy: str = "dos",
    mesh_shape=(1, 1),
    seed: int = 0,
    greedy: bool = True,
):
    mesh = make_test_mesh(*mesh_shape)
    rules = ShardingRules(mesh, strategy=strategy, fsdp=False)
    model = build(cfg)
    max_len = prompt_len + gen_tokens

    with use_rules(rules), mesh:
        ps = param_sharding(model.defs, rules)
        params = jax.device_put(model.init(jax.random.PRNGKey(seed)), ps)

        rng = jax.random.PRNGKey(seed + 1)
        prompts = jax.random.randint(rng, (batch, prompt_len), 0, cfg.vocab)
        pf_batch = {"tokens": prompts}
        if cfg.family == "vlm":
            pf_batch["image_embeds"] = jax.random.normal(
                rng, (batch, cfg.n_image_tokens, cfg.d_model),
                dtype=jnp.dtype(cfg.compute_dtype),
            )
        if cfg.family == "encdec":
            pf_batch["enc_frames"] = jax.random.normal(
                rng, (batch, cfg.enc_seq, cfg.d_model),
                dtype=jnp.dtype(cfg.compute_dtype),
            )

        prefill = jax.jit(lambda p, b: model.prefill(p, b, max_len=max_len))
        decode = jax.jit(model.decode)

        t0 = time.time()
        logits, cache = prefill(params, pf_batch)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        tok = jnp.argmax(logits[:, -1:], axis=-1)
        out_tokens = [tok]
        # Per-step timing: synchronize every decode step so the
        # percentiles measure real step latency (the first step carries
        # the jit compile; it is kept — p99 reports it honestly, the
        # median ignores it).
        step_s = []
        t0 = time.time()
        for _ in range(gen_tokens - 1):
            ts = time.time()
            logits, cache = decode(params, cache, {"token": tok})
            tok = jnp.argmax(logits, axis=-1)
            tok.block_until_ready()
            step_s.append(time.time() - ts)
            out_tokens.append(tok)
        t_decode = time.time() - t0

    gen = jnp.concatenate(out_tokens, axis=1)
    steps = jnp.asarray(step_s) if step_s else jnp.zeros(1)
    return {
        "generated": gen,
        "last_logits": logits,
        "prefill_s": t_prefill,
        "decode_s": t_decode,
        "decode_tok_s": batch * (gen_tokens - 1) / max(t_decode, 1e-9),
        "step_p50_s": float(jnp.percentile(steps, 50)),
        "step_p99_s": float(jnp.percentile(steps, 99)),
    }


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-tokens", type=int, default=32)
    ap.add_argument("--strategy", default="dos")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable summary (CI smoke)")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    r = serve_loop(
        cfg, batch=args.batch, prompt_len=args.prompt_len,
        gen_tokens=args.gen_tokens, strategy=args.strategy,
    )
    if args.json:
        print(json.dumps({
            "arch": args.arch,
            "batch": args.batch,
            "prompt_len": args.prompt_len,
            "gen_tokens": args.gen_tokens,
            "prefill_s": r["prefill_s"],
            "decode_tok_s": r["decode_tok_s"],
            "step_p50_s": r["step_p50_s"],
            "step_p99_s": r["step_p99_s"],
        }, indent=1))
        return
    print(
        f"prefill {r['prefill_s']*1e3:.1f}ms; decode {r['decode_tok_s']:.1f} tok/s "
        f"(step p50 {r['step_p50_s']*1e3:.2f}ms, p99 {r['step_p99_s']*1e3:.2f}ms); "
        f"sample: {r['generated'][0, :16].tolist()}"
    )


if __name__ == "__main__":
    main()
