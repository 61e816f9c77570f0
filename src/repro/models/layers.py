"""Shared transformer layers: norms, RoPE, attention, MLP, embeddings.

Pure-functional: every block is ``(params, x, ...) -> y`` with params
described by ParamDef trees. All GEMMs route through ``proj`` which
dispatches to the dOS Pallas kernel on TPU and plain jnp elsewhere.
Activations carry logical sharding constraints (``parallel.axes.shard``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..kernels.dos_matmul import dos_matmul
from ..kernels.flash_attention import decode_attention, flash_attention
from ..parallel.axes import shard
from .params import ParamDef

__all__ = [
    "proj", "rmsnorm", "rmsnorm_def", "rope", "embed_defs", "embed_tokens",
    "unembed", "attn_defs", "attention", "mlp_defs", "mlp",
]


def proj(x, w, b=None):
    """x (..., K) @ w (K, N) in compute dtype, f32 accumulation.

    The cast weight is checkpoint-named so the `save_gathered` remat
    policy can keep FSDP/ZeRO all-gather results across the backward
    pass instead of re-gathering (§Perf A3)."""
    w_c = checkpoint_name(w.astype(x.dtype), "gathered_w")
    y = dos_matmul(x, w_c, out_dtype=x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


# --- norms ---------------------------------------------------------------


def rmsnorm_def(dim: int, axes=("embed",)):
    return ParamDef((dim,), axes, init="ones" if len(axes) else "ones")


def rmsnorm(x, scale, eps: float = 1e-6, plus_one: bool = False):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    s = scale.astype(jnp.float32)
    if plus_one:
        s = 1.0 + s
    return (y * s).astype(x.dtype)


# --- rotary embeddings -----------------------------------------------------


def rope(x, positions, theta):
    """x: (..., S, H, D); positions: (S,) or scalar; theta may be traced
    (per-layer theta arrays inside scanned layers)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(
        -jnp.log(jnp.asarray(theta, jnp.float32))
        * (jnp.arange(half, dtype=jnp.float32) / half)
    )
    ang = jnp.asarray(positions, jnp.float32)[..., None] * freqs  # (S, half)
    cos = jnp.cos(ang)[..., None, :]  # (S, 1, half)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --- embeddings -------------------------------------------------------------


def embed_defs(cfg):
    defs = {"tok": ParamDef((cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02)}
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef(
            (cfg.d_model, cfg.vocab), ("embed", "vocab"), contract=0, out=1
        )
    return defs


def embed_tokens(p, tokens, compute_dtype):
    x = jnp.take(p["tok"], tokens, axis=0).astype(compute_dtype)
    return shard(x, "residual")


def unembed(p, x, tie: bool):
    w = p["tok"].T if tie else p["head"]
    with jax.named_scope("logits"):
        logits = proj(x.astype(jnp.bfloat16) if x.dtype == jnp.bfloat16 else x, w)
    return shard(logits.astype(jnp.float32), "logits")


# --- attention ---------------------------------------------------------------


def attn_defs(cfg, cross: bool = False):
    e, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    defs = {
        "wq": ParamDef((e, h * hd), ("embed", "heads_flat"), contract=0, out=1),
        "wk": ParamDef((e, kvh * hd), ("embed", "heads_flat"), contract=0, out=1),
        "wv": ParamDef((e, kvh * hd), ("embed", "heads_flat"), contract=0, out=1),
        "wo": ParamDef((h * hd, e), ("heads_flat", "embed"), contract=0, out=1),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h * hd,), ("heads_flat",), init="zeros")
        defs["bk"] = ParamDef((kvh * hd,), ("heads_flat",), init="zeros")
        defs["bv"] = ParamDef((kvh * hd,), ("heads_flat",), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = rmsnorm_def(hd, ("head_dim",))
        defs["k_norm"] = rmsnorm_def(hd, ("head_dim",))
    return defs


def compute_cross_kv(p, kv_src, cfg):
    """Project a cross-attention source (image embeds / encoder output)
    to (k, v) once — cached at prefill, reused every decode step."""
    b, skv, _ = kv_src.shape
    kvh, hd = cfg.n_kv_heads, cfg.head_dim_
    k = proj(kv_src, p["wk"], p.get("bk")).reshape(b, skv, kvh, hd)
    v = proj(kv_src, p["wv"], p.get("bv")).reshape(b, skv, kvh, hd)
    return shard(k, "kv_cache"), shard(v, "kv_cache")


def attention(
    p,
    x,
    cfg,
    *,
    mode: str,  # train | prefill | decode
    positions=None,  # rope positions for x
    window=None,  # None/0 = global; traced scalar OK (jnp mask path)
    theta=None,  # rope theta (traced OK); None -> no rope (whisper sin)
    cache=None,  # dict(k, v, length) for decode / filled by prefill
    cross_kv=None,  # precomputed (k, v) -> cross-attention, no cache update
    causal: bool = True,
):
    """The universal attention block. Returns (y, new_cache)."""
    b, s, e = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_

    q = proj(x, p["wq"], p.get("bq")).reshape(b, s, h, hd)
    if cross_kv is None:
        k = proj(x, p["wk"], p.get("bk")).reshape(b, s, kvh, hd)
        v = proj(x, p["wv"], p.get("bv")).reshape(b, s, kvh, hd)
    else:
        k, v = cross_kv

    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        if cross_kv is None:
            k = rmsnorm(k, p["k_norm"], cfg.norm_eps)

    win = window  # may be a traced scalar; the jnp mask path handles it

    new_cache = None
    if cross_kv is not None:
        q = shard(q, "attn_heads")
        skv = k.shape[1]
        if mode == "decode":
            o = decode_attention(q, k, v, length=skv, window=None)
        else:
            o = flash_attention(
                q, k, v, causal=False, window=None, unroll=cfg.unroll_inner
            )
    elif mode == "decode":
        assert cache is not None and s == 1
        length = cache["length"]
        if theta is not None:
            q = rope(q, length, theta)
            k = rope(k, length, theta)
        q = shard(q, "attn_heads")
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, length, 0, 0)
        )
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, length, 0, 0)
        )
        kc = shard(kc, "kv_cache")
        vc = shard(vc, "kv_cache")
        o = decode_attention(q, kc, vc, length=length + 1, window=win)
        new_cache = {"k": kc, "v": vc, "length": length + 1}
    else:
        if theta is not None:
            if positions is None:
                positions = jnp.arange(s)
            q = rope(q, positions, theta)
            k = rope(k, positions, theta)
        q = shard(q, "attn_heads")
        k = shard(k, "kv_cache")
        v = shard(v, "kv_cache")
        o = flash_attention(
            q, k, v, causal=causal, window=win, unroll=cfg.unroll_inner
        )
        if mode == "prefill":
            new_cache = {"k": k, "v": v, "length": jnp.int32(s)}

    o = shard(o, "attn_heads")
    y = proj(o.reshape(b, s, h * hd), p["wo"])
    return shard(y, "residual"), new_cache


# --- MLP -----------------------------------------------------------------------


def mlp_defs(cfg, d_ff=None, act=None):
    e = cfg.d_model
    f = d_ff or cfg.d_ff
    act = act or cfg.act
    if act == "silu":  # gated (llama family)
        return {
            "wi_gate": ParamDef((e, f), ("embed", "mlp"), contract=0, out=1),
            "wi_up": ParamDef((e, f), ("embed", "mlp"), contract=0, out=1),
            "wo": ParamDef((f, e), ("mlp", "embed"), contract=0, out=1),
        }
    return {  # plain 2-layer (whisper)
        "wi": ParamDef((e, f), ("embed", "mlp"), contract=0, out=1),
        "bi": ParamDef((f,), ("mlp",), init="zeros"),
        "wo": ParamDef((f, e), ("mlp", "embed"), contract=0, out=1),
        "bo": ParamDef((e,), ("embed",), init="zeros"),
    }


def mlp(p, x, act: str = "silu"):
    if "wi_gate" in p:  # scopes name the GEMMs as core.network does
        with jax.named_scope("mlp.in"):
            g = proj(x, p["wi_gate"])
            u = proj(x, p["wi_up"])
        hidden = shard(jax.nn.silu(g) * u, "mlp_hidden")
        with jax.named_scope("mlp.out"):
            y = proj(hidden, p["wo"])
    else:
        hidden = shard(jax.nn.gelu(proj(x, p["wi"], p["bi"])), "mlp_hidden")
        y = proj(hidden, p["wo"], p["bo"])
    return shard(y, "residual")
