"""Shared transformer layers: norms, RoPE, attention, MLP, embeddings.

Pure-functional: every block is ``(params, x, ...) -> y`` with params
described by ParamDef trees. All GEMMs route through ``proj`` which
dispatches to the dOS Pallas kernel on TPU and plain jnp elsewhere.
Weight GEMMs run under named scopes carrying their names in the lowered
stream (``attn.q``, ``mlp.in`` ...), so a traced jaxpr can be held
against ``core.network``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.dos_matmul import dos_matmul
from ..kernels.flash_attention import decode_attention, flash_attention
from .params import ParamDef

__all__ = [
    "proj", "rmsnorm", "rmsnorm_def", "rope", "embed_defs", "embed_tokens",
    "unembed", "attn_defs", "attention", "mlp_defs", "mlp",
]


def proj(x, w, b=None):
    """x (..., K) @ w (K, N) in compute dtype, f32 accumulation."""
    y = dos_matmul(x, w.astype(x.dtype), out_dtype=x.dtype)
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


# --- norms ---------------------------------------------------------------


def rmsnorm_def(dim: int):
    return ParamDef((dim,), init="ones")


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
    defs = {"tok": ParamDef((cfg.vocab, cfg.d_model), scale=0.02)}
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((cfg.d_model, cfg.vocab), contract=0)
    return defs


def embed_tokens(p, tokens, compute_dtype):
    return jnp.take(p["tok"], tokens, axis=0).astype(compute_dtype)


def unembed(p, x, tie: bool):
    w = p["tok"].T if tie else p["head"]
    with jax.named_scope("logits"):
        logits = proj(x.astype(jnp.bfloat16) if x.dtype == jnp.bfloat16 else x, w)
    return logits.astype(jnp.float32)


# --- attention ---------------------------------------------------------------


def attn_defs(cfg, cross: bool = False):
    e, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    defs = {
        "wq": ParamDef((e, h * hd), contract=0),
        "wk": ParamDef((e, kvh * hd), contract=0),
        "wv": ParamDef((e, kvh * hd), contract=0),
        "wo": ParamDef((h * hd, e), contract=0),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((h * hd,), init="zeros")
        defs["bk"] = ParamDef((kvh * hd,), init="zeros")
        defs["bv"] = ParamDef((kvh * hd,), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = rmsnorm_def(hd)
        defs["k_norm"] = rmsnorm_def(hd)
    return defs


def compute_cross_kv(p, kv_src, cfg):
    """Project a cross-attention source (image embeds / encoder output)
    to (k, v) once — cached at prefill, reused every decode step."""
    b, skv, _ = kv_src.shape
    kvh, hd = cfg.n_kv_heads, cfg.head_dim_
    with jax.named_scope("cross.kv"):
        k = proj(kv_src, p["wk"], p.get("bk")).reshape(b, skv, kvh, hd)
        v = proj(kv_src, p["wv"], p.get("bv")).reshape(b, skv, kvh, hd)
    return k, v


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
    scope = "attn" if cross_kv is None else "cross"

    with jax.named_scope(f"{scope}.q"):
        q = proj(x, p["wq"], p.get("bq")).reshape(b, s, h, hd)
    if cross_kv is None:
        with jax.named_scope("attn.kv"):
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
        skv = k.shape[1]
        if mode == "decode":
            o = decode_attention(q, k, v, length=skv, window=None)
        else:
            o = flash_attention(q, k, v, causal=False, window=None)
    elif mode == "decode":
        assert cache is not None and s == 1
        length = cache["length"]
        if theta is not None:
            q = rope(q, length, theta)
            k = rope(k, length, theta)
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, length, 0, 0)
        )
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, length, 0, 0)
        )
        o = decode_attention(q, kc, vc, length=length + 1, window=win)
        new_cache = {"k": kc, "v": vc, "length": length + 1}
    else:
        if theta is not None:
            if positions is None:
                positions = jnp.arange(s)
            q = rope(q, positions, theta)
            k = rope(k, positions, theta)
        o = flash_attention(q, k, v, causal=causal, window=win)
        if mode == "prefill":
            new_cache = {"k": k, "v": v, "length": jnp.int32(s)}

    with jax.named_scope(f"{scope}.o"):
        y = proj(o.reshape(b, s, h * hd), p["wo"])
    return y, new_cache


# --- MLP -----------------------------------------------------------------------


def mlp_defs(cfg, d_ff=None, act=None):
    e = cfg.d_model
    f = d_ff or cfg.d_ff
    act = act or cfg.act
    if act == "silu":  # gated (llama family)
        return {
            "wi_gate": ParamDef((e, f), contract=0),
            "wi_up": ParamDef((e, f), contract=0),
            "wo": ParamDef((f, e), contract=0),
        }
    return {  # plain 2-layer (whisper)
        "wi": ParamDef((e, f), contract=0),
        "bi": ParamDef((f,), init="zeros"),
        "wo": ParamDef((f, e), contract=0),
        "bo": ParamDef((e,), init="zeros"),
    }


def mlp(p, x, act: str = "silu"):
    if "wi_gate" in p:  # scopes name the GEMMs as core.network does
        with jax.named_scope("mlp.in"):
            g = proj(x, p["wi_gate"])
            u = proj(x, p["wi_up"])
        with jax.named_scope("mlp.out"):
            return proj(jax.nn.silu(g) * u, p["wo"])
    hidden = jax.nn.gelu(proj(x, p["wi"], p["bi"]))
    return proj(hidden, p["wo"], p["bo"])
