"""Expert-parallel MoE dispatch (shard_map) — §Perf Cell C2.

Fine-grained MoE (deepseek: 64 experts, per-expert K=1408) sits in the
paper's *small-K loses* regime (Fig. 5): no tensor axis wants a slice
of an expert. The right mapping keeps experts **whole but distributed**
— 64/16 = 4 experts per device over the ``model`` axis — and moves
*tokens* to experts instead of gathering weights:

  - every device routes its local tokens (router weights replicated);
  - tokens pick top-k experts; picks for non-local experts are masked
    into a zero-weight overflow bucket;
  - a ragged_dot over the 4 local experts computes local contributions;
  - a psum over ``model`` combines (each token's k experts live
    somewhere, every device contributes what it owns).

Wire cost per layer ≈ one psum of the token activations (tokens x E),
independent of expert-parameter size — vs. the ZeRO mapping's
per-layer gather of the full expert set (measured: 120 TB/step,
EXPERIMENTS.md §Perf C1, refuted).

This module is validated against the replicated ``moe_block`` oracle in
tests/test_sharding_multidevice.py (smoke scale).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..models.layers import proj

__all__ = ["moe_block_ep"]


def moe_block_ep(p, x, cfg, mesh, *, axis: str = "model", batch_axis: str | None = "data"):
    """Expert-parallel MoE FFN. p: the moe_defs tree with expert weights
    sharded over ``axis`` on their expert dim; x: (B, S, E) sharded over
    ``batch_axis``. Returns (B, S, E)."""
    ne = cfg.n_experts
    ax_size = mesh.shape[axis]
    assert ne % ax_size == 0, (ne, ax_size)
    ne_local = ne // ax_size
    k = cfg.top_k

    in_specs = (
        {  # params
            "router": P(),
            "wi_gate": P(axis),
            "wi_up": P(axis),
            "wo": P(axis),
            **({"shared": P()} if "shared" in p else {}),
        },
        P(batch_axis),  # x
    )

    @functools.partial(
        jax.shard_map, mesh=mesh, in_specs=in_specs, out_specs=P(batch_axis),
    )
    def run(pl, xl):
        b, s, e = xl.shape
        t = b * s
        xt = xl.reshape(t, e)
        my = jax.lax.axis_index(axis)

        logits = xt.astype(jnp.float32) @ pl["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        topk_p, topk_i = jax.lax.top_k(probs, k)
        topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)

        # local expert ids in [0, ne_local); non-local -> overflow bucket
        local_i = topk_i - my * ne_local
        is_local = (local_i >= 0) & (local_i < ne_local)
        local_i = jnp.where(is_local, local_i, ne_local)
        w_local = jnp.where(is_local, topk_p, 0.0)

        flat_e = local_i.reshape(-1)
        order = jnp.argsort(flat_e)
        token_of = jnp.arange(t * k, dtype=jnp.int32) // k
        xs = xt[token_of[order]]
        group_sizes = jnp.bincount(flat_e, length=ne_local + 1).astype(jnp.int32)

        # zero-expert overflow row keeps ragged_dot shapes static
        def padded(w):  # (ne_local, a, b) -> (ne_local + 1, a, b)
            return jnp.concatenate([w, jnp.zeros_like(w[:1])], axis=0)

        g = jax.lax.ragged_dot(xs, padded(pl["wi_gate"]).astype(xs.dtype), group_sizes)
        u = jax.lax.ragged_dot(xs, padded(pl["wi_up"]).astype(xs.dtype), group_sizes)
        h = jax.nn.silu(g) * u
        y_sorted = jax.lax.ragged_dot(h, padded(pl["wo"]).astype(h.dtype), group_sizes)

        inv = jnp.argsort(order)
        y = y_sorted[inv].reshape(t, k, e)
        y = jnp.sum(y * w_local[..., None].astype(y.dtype), axis=1)
        # combine across expert shards: each device contributed the
        # experts it owns — the psum is the paper's adder pile applied
        # to the *expert* axis.
        y = jax.lax.psum(y, axis)

        if "shared" in pl:
            sp = pl["shared"]
            sg = proj(xt, sp["wi_gate"])
            su = proj(xt, sp["wi_up"])
            y = y + proj(jax.nn.silu(sg) * su, sp["wo"]).astype(y.dtype)
        return y.reshape(b, s, e).astype(xl.dtype)

    pl_in = {kk: p[kk] for kk in ("router", "wi_gate", "wi_up", "wo")}
    if "shared" in p:
        pl_in["shared"] = p["shared"]
    return run(pl_in, x)
