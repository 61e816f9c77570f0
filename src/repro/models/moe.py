"""Mixture-of-Experts FFN (deepseek-moe fine-grained; llama4-scout).

Routing: softmax router -> top-k experts per token -> tokens sorted by
expert id -> ``jax.lax.ragged_dot`` over expert groups (dense MXU
per-group GEMMs, no capacity-dropping) -> unsort, weight, combine.
Shared experts (deepseek's always-on experts) run as a plain gated MLP.
DeepSeek-V3 routes as its ``inference/model.py`` Gate does: sigmoid
scores plus a per-expert bias pick the experts, within the
``topk_groups`` groups whose two best biased scores sum highest; the
chosen experts' unbiased scores, normalized to sum to one, are scaled
by ``routed_scale``.

Each weight GEMM runs under a named scope carrying its name in the
lowered stream (``moe.router``, ``moe.expert.in`` ...), so a traced
jaxpr can be held against ``core.network``.

Paper connection: each routed expert GEMM has K = expert_d_ff (tiny for
fine-grained MoE). The advisor (core.advisor) correctly scores dOS as
unattractive here — the paper's small-K finding (Fig. 5, green curves).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import proj
from .params import ParamDef

__all__ = ["moe_defs", "moe_block"]


def moe_defs(cfg):
    e = cfg.d_model
    f = cfg.expert_d_ff
    ne = cfg.n_experts
    defs = {
        "router": ParamDef((e, ne), contract=0),
        "wi_gate": ParamDef((ne, e, f), contract=1),
        "wi_up": ParamDef((ne, e, f), contract=1),
        "wo": ParamDef((ne, f, e), contract=1),
    }
    if cfg.router_score == "sigmoid":  # selection bias (aux-loss-free balancing)
        defs["router_bias"] = ParamDef((ne,), init="zeros")
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        defs["shared"] = {
            "wi_gate": ParamDef((e, fs), contract=0),
            "wi_up": ParamDef((e, fs), contract=0),
            "wo": ParamDef((fs, e), contract=0),
        }
    return defs


def _route(p, xt, cfg):
    """Top-k routing of tokens xt (T, E): (weights (T, K), experts (T, K))."""
    with jax.named_scope("moe.router"):
        logits = proj(xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    k = cfg.top_k
    if cfg.router_score != "sigmoid":
        probs = jax.nn.softmax(logits, axis=-1)  # (T, NE)
        topk_p, topk_i = jax.lax.top_k(probs, k)  # (T, K)
        return topk_p / jnp.sum(topk_p, axis=-1, keepdims=True), topk_i
    scores = jax.nn.sigmoid(logits)
    biased = scores + p["router_bias"].astype(jnp.float32)
    if cfg.n_expert_groups > 1:
        t, ng = xt.shape[0], cfg.n_expert_groups
        grouped = biased.reshape(t, ng, -1)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)  # (T, G)
        _, keep = jax.lax.top_k(group_score, cfg.topk_groups)
        kept = jnp.any(keep[..., None] == jnp.arange(ng), axis=1)  # (T, G)
        biased = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(t, -1)
    _, topk_i = jax.lax.top_k(biased, k)
    topk_p = jnp.take_along_axis(scores, topk_i, axis=-1)
    topk_p = topk_p / jnp.sum(topk_p, axis=-1, keepdims=True)
    return topk_p * cfg.routed_scale, topk_i


def moe_block(p, x, cfg):
    """x: (B, S, E) -> (B, S, E)."""
    b, s, e = x.shape
    t = b * s
    k = cfg.top_k
    ne = cfg.n_experts
    xt = x.reshape(t, e)

    # --- routing (f32 for numerics) ---------------------------------------
    topk_p, topk_i = _route(p, xt, cfg)

    # --- sort-by-expert dispatch ------------------------------------------
    flat_expert = topk_i.reshape(-1)  # (T*K,)
    order = jnp.argsort(flat_expert)  # stable
    token_of = jnp.arange(t * k, dtype=jnp.int32) // k
    xs = xt[token_of[order]]  # (T*K, E) sorted by expert
    group_sizes = jnp.bincount(flat_expert, length=ne).astype(jnp.int32)

    # --- expert GEMMs (ragged over groups) ----------------------------------
    with jax.named_scope("moe.expert.in"):
        g = jax.lax.ragged_dot(xs, p["wi_gate"].astype(xs.dtype), group_sizes)
        u = jax.lax.ragged_dot(xs, p["wi_up"].astype(xs.dtype), group_sizes)
    h = jax.nn.silu(g) * u  # (T*K, F)
    with jax.named_scope("moe.expert.out"):
        y_sorted = jax.lax.ragged_dot(h, p["wo"].astype(h.dtype), group_sizes)

    # --- unsort & combine ------------------------------------------------------
    inv = jnp.argsort(order)
    y = y_sorted[inv]  # (T*K, E) in (token, k) order
    y = y.reshape(t, k, e) * topk_p[..., None].astype(y.dtype)
    y = jnp.sum(y, axis=1)  # (T, E)

    if "shared" in p:
        sp = p["shared"]
        with jax.named_scope("moe.shared.in"):
            sg = proj(xt, sp["wi_gate"])
            su = proj(xt, sp["wi_up"])
        with jax.named_scope("moe.shared.out"):
            y = y + proj(jax.nn.silu(sg) * su, sp["wo"])

    return y.reshape(b, s, e).astype(x.dtype)


def aux_load_balance_loss(p, x, cfg):
    """Switch-style load-balance auxiliary loss (added by ``Model.loss``)."""
    b, s, e = x.shape
    xt = x.reshape(b * s, e)
    logits = proj(xt.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top1 = jnp.argmax(probs, axis=-1)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(top1, cfg.n_experts, dtype=jnp.float32), axis=0
    )
    frac_probs = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac_tokens * frac_probs)
