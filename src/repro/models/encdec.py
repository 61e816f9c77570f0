"""Whisper-style encoder-decoder backbone.

The conv/mel frontend is a STUB: the batch's ``enc_frames`` are
precomputed frame embeddings (B, enc_seq, E). The encoder is a
bidirectional transformer over frames; the decoder is causal self-attn
+ cross-attn to the encoder output. Positions are sinusoidal (keeps
parameter shapes independent of the benchmark sequence lengths).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config import ArchConfig
from .layers import (
    attention, attn_defs, compute_cross_kv, embed_defs, embed_tokens,
    mlp, mlp_defs, rmsnorm, rmsnorm_def, unembed,
)
from .params import stack_defs

__all__ = ["encdec_defs", "encode", "encdec_forward", "encdec_init_cache"]


def _sinusoid(seq, dim, offset=0):
    pos = jnp.arange(seq, dtype=jnp.float32)[:, None] + offset
    half = dim // 2
    freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (jnp.log(10_000.0) / max(half - 1, 1)))
    ang = pos * freq[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _enc_block_defs(cfg):
    return {
        "ln1": rmsnorm_def(cfg.d_model),
        "attn": attn_defs(cfg),
        "ln2": rmsnorm_def(cfg.d_model),
        "ffn": mlp_defs(cfg, act="gelu"),
    }


def _dec_block_defs(cfg):
    return {
        "ln1": rmsnorm_def(cfg.d_model),
        "attn": attn_defs(cfg),
        "lnx": rmsnorm_def(cfg.d_model),
        "xattn": attn_defs(cfg),
        "ln2": rmsnorm_def(cfg.d_model),
        "ffn": mlp_defs(cfg, act="gelu"),
    }


def encdec_defs(cfg: ArchConfig):
    return {
        "embed": embed_defs(cfg),
        "enc_layers": stack_defs(_enc_block_defs(cfg), cfg.n_enc_layers),
        "enc_norm": rmsnorm_def(cfg.d_model),
        "dec_layers": stack_defs(_dec_block_defs(cfg), cfg.n_layers),
        "final_norm": rmsnorm_def(cfg.d_model),
    }


def encode(params, frames, cfg: ArchConfig):
    """frames: (B, enc_seq, E) precomputed stub embeddings."""
    x = frames.astype(jnp.dtype(cfg.compute_dtype))
    x = x + _sinusoid(x.shape[1], x.shape[2]).astype(x.dtype)[None]

    def body(carry, lp):
        h, _ = attention(
            lp["attn"], rmsnorm(carry, lp["ln1"], cfg.norm_eps), cfg,
            mode="train", causal=False, theta=None,
        )
        y = carry + h
        y = y + mlp(lp["ffn"], rmsnorm(y, lp["ln2"], cfg.norm_eps), "gelu")
        return y, None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return rmsnorm(x, params["enc_norm"], cfg.norm_eps)


def encdec_init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    kvh, hd = cfg.n_kv_heads, cfg.head_dim_
    L = cfg.n_layers
    kv = {
        "k": jnp.zeros((L, batch, max_len, kvh, hd), dtype),
        "v": jnp.zeros((L, batch, max_len, kvh, hd), dtype),
        "length": jnp.zeros((L,), jnp.int32),
    }
    cross = {
        "k": jnp.zeros((L, batch, cfg.enc_seq, kvh, hd), dtype),
        "v": jnp.zeros((L, batch, cfg.enc_seq, kvh, hd), dtype),
    }
    return {"self": kv, "cross": cross}


def encdec_forward(
    params,
    tokens,  # (B, S) decoder tokens
    cfg: ArchConfig,
    *,
    mode: str,
    enc_frames=None,  # (B, enc_seq, E); required for train/prefill
    cache=None,
    max_len: int = 0,
):
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, compute_dtype)
    b, s, e = x.shape

    if mode == "decode":
        offset = cache["self"]["length"][0]  # same for all layers
    else:
        offset = 0
        enc_out = encode(params, enc_frames, cfg)
    x = x + _sinusoid(s, e, offset=offset).astype(x.dtype)[None]

    self_caches = cache["self"] if cache is not None else None
    cross_caches = cache["cross"] if cache is not None else None

    def body(carry, xs):
        lp, sc, cc = xs
        h, new_sc = attention(
            lp["attn"], rmsnorm(carry, lp["ln1"], cfg.norm_eps), cfg,
            mode=mode, cache=sc, theta=None,
        )
        y = carry + h
        if mode == "decode":
            ckv = (cc["k"], cc["v"])
        else:
            ckv = compute_cross_kv(lp["xattn"], enc_out, cfg)
        hx, _ = attention(
            lp["xattn"], rmsnorm(y, lp["lnx"], cfg.norm_eps), cfg,
            mode=mode, cross_kv=ckv,
        )
        y = y + hx
        y = y + mlp(lp["ffn"], rmsnorm(y, lp["ln2"], cfg.norm_eps), "gelu")
        new_cc = None if mode == "train" else {"k": ckv[0], "v": ckv[1]}
        return y, (new_sc, new_cc)

    x, (new_self, new_cross) = jax.lax.scan(
        body, x, (params["dec_layers"], self_caches, cross_caches)
    )

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings)

    new_cache = None
    if mode != "train":
        new_cache = {"self": new_self, "cross": new_cross}
        if mode == "prefill" and max_len:
            padw = max_len - new_cache["self"]["k"].shape[-3]
            if padw > 0:
                pw = [(0, 0)] * new_cache["self"]["k"].ndim
                pw[-3] = (0, padw)
                new_cache["self"] = {
                    "k": jnp.pad(new_cache["self"]["k"], pw),
                    "v": jnp.pad(new_cache["self"]["v"], pw),
                    "length": new_cache["self"]["length"],
                }
    return logits, new_cache
