"""Multi-head latent attention (DeepSeek-V2/V3), in plain jax.numpy.

The plain reference the lowering's ``core.network._mla`` is checked
against: no kernels, every weight GEMM a ``@`` under a named scope that
carries its stream name (``attn.q_a`` ... ``attn.o``), so a traced
jaxpr shows which GEMM is which. Run it in float32 under
``jax.default_matmul_precision("highest")`` where it serves as the
reference (a TPU multiplies float32 in bfloat16 passes otherwise).

Per token, q comes from a low-rank latent (q_a: d -> q_lora, RMS norm,
q_b: q_lora -> heads x (nope + rope)); kv_a projects the input to the
kv latent c (kv_lora, RMS-normed) and one rope key k_pe shared by all
heads. Two paths, as DeepSeek-V3's ``inference/model.py``:

- naive (train, prefill): kv_b expands c to each head's nope key and
  value; scores q_nope.k_nope + q_pe.k_pe over the causal context.
- absorbed (decode, ``attn_impl="absorb"``): kv_b is split per head
  into W_UK (nope -> latent) and W_UV (latent -> v). The query's nope
  part is carried into the latent space (attn.uk), scored against the
  cached c directly, and the latent attention output is carried back
  out (attn.uv). The cache holds only c and k_pe per token.

Departures from the published model, none of which changes a GEMM:
YaRN rope scaling is replaced by plain RoPE at ``rope_theta``, and the
softmax scale is 1/sqrt(nope + rope) without YaRN's mscale.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import rmsnorm, rmsnorm_def, rope
from .params import ParamDef

__all__ = ["mla_defs", "mla_attention", "mla_init_cache"]


def _mm(x, w, name: str):
    """One weight GEMM, ``x @ w``, named as in the lowered stream."""
    with jax.named_scope(name):
        return x @ w.astype(x.dtype)


def mla_defs(cfg):
    e, h = cfg.d_model, cfg.n_heads
    dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    return {
        "wq_a": ParamDef((e, ql), ("embed", "q_lora"), contract=0, out=1),
        "q_norm": rmsnorm_def(ql, ("q_lora",)),
        "wq_b": ParamDef((ql, h * (dn + dr)), ("q_lora", "heads_flat"), contract=0, out=1),
        "wkv_a": ParamDef((e, kl + dr), ("embed", "kv_lora"), contract=0, out=1),
        "kv_norm": rmsnorm_def(kl, ("kv_lora",)),
        "wkv_b": ParamDef((kl, h * (dn + dv)), ("kv_lora", "heads_flat"), contract=0, out=1),
        "wo": ParamDef((h * dv, e), ("heads_flat", "embed"), contract=0, out=1),
    }


def mla_init_cache(cfg, batch: int, max_len: int, dtype):
    """Latent cache of one layer: c (B, S, kv_lora), k_pe (B, S, rope)."""
    return {
        "c": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
        "k_pe": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), dtype),
        "length": jnp.int32(0),
    }


def _query(p, x, cfg, positions, theta):
    """(q_nope (B, S, H, nope), q_pe (B, S, H, rope)), q_pe rotated."""
    b, s, _ = x.shape
    dn = cfg.qk_nope_head_dim
    q_lat = rmsnorm(_mm(x, p["wq_a"], "attn.q_a"), p["q_norm"], cfg.norm_eps)
    q = _mm(q_lat, p["wq_b"], "attn.q_b").reshape(b, s, cfg.n_heads, -1)
    return q[..., :dn], rope(q[..., dn:], positions, theta)


def _latent(p, x, cfg, positions, theta):
    """(c (B, S, kv_lora), k_pe (B, S, rope)): what the cache holds."""
    kl = cfg.kv_lora_rank
    kv = _mm(x, p["wkv_a"], "attn.kv_a")
    c = rmsnorm(kv[..., :kl], p["kv_norm"], cfg.norm_eps)
    k_pe = rope(kv[..., None, kl:], positions, theta)[..., 0, :]
    return c, k_pe


def _softmax(scores, mask):
    scores = jnp.where(mask, scores.astype(jnp.float32), -jnp.inf)
    return jax.nn.softmax(scores, axis=-1)


def mla_attention(p, x, cfg, *, mode: str, cache=None, theta=None):
    """x (B, S, E) -> (y (B, S, E), new_cache). ``mode`` picks the
    path: naive for train/prefill (prefill returns the latent cache),
    absorbed for decode (one token against ``cache``)."""
    b, s, _ = x.shape
    h, dn, dv = cfg.n_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    theta = cfg.rope_theta if theta is None else theta
    scale = (dn + cfg.qk_rope_head_dim) ** -0.5
    new_cache = None
    if mode == "decode":
        assert cache is not None and s == 1
        pos = cache["length"]
        q_nope, q_pe = _query(p, x, cfg, pos, theta)
        c, k_pe = _latent(p, x, cfg, pos, theta)
        cc = jax.lax.dynamic_update_slice(
            cache["c"], c.astype(cache["c"].dtype), (0, pos, 0))
        kc = jax.lax.dynamic_update_slice(
            cache["k_pe"], k_pe.astype(cache["k_pe"].dtype), (0, pos, 0))
        new_cache = {"c": cc, "k_pe": kc, "length": pos + 1}
        w = p["wkv_b"].reshape(cfg.kv_lora_rank, h, dn + dv).transpose(1, 0, 2)
        w_uk = w[..., :dn].transpose(0, 2, 1)  # (H, nope, kv_lora)
        w_uv = w[..., dn:]  # (H, kv_lora, v)
        q_lat = _mm(q_nope.transpose(0, 2, 1, 3), w_uk, "attn.uk")  # (B, H, S, kv_lora)
        cc, kc = cc.astype(x.dtype), kc.astype(x.dtype)
        scores = (jnp.einsum("bhsc,btc->bhst", q_lat, cc)
                  + jnp.einsum("bshr,btr->bhst", q_pe, kc)) * scale
        mask = jnp.arange(cc.shape[1]) <= pos
        probs = _softmax(scores, mask).astype(x.dtype)
        o_lat = jnp.einsum("bhst,btc->bhsc", probs, cc)
        o = _mm(o_lat, w_uv, "attn.uv").transpose(0, 2, 1, 3)  # (B, S, H, v)
    else:
        positions = jnp.arange(s)
        q_nope, q_pe = _query(p, x, cfg, positions, theta)
        c, k_pe = _latent(p, x, cfg, positions, theta)
        kv = _mm(c, p["wkv_b"], "attn.kv_b").reshape(b, s, h, dn + dv)
        k_nope, v = kv[..., :dn], kv[..., dn:]
        scores = (jnp.einsum("bshd,bthd->bhst", q_nope, k_nope)
                  + jnp.einsum("bshr,btr->bhst", q_pe, k_pe)) * scale
        mask = positions[None, :] <= positions[:, None]
        probs = _softmax(scores, mask).astype(x.dtype)
        o = jnp.einsum("bhst,bthd->bshd", probs, v)
        if mode == "prefill":
            new_cache = {"c": c, "k_pe": k_pe, "length": jnp.int32(s)}
    y = _mm(o.reshape(b, s, h * dv), p["wo"], "attn.o")
    return y, new_cache
