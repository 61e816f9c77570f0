"""Pallas TPU kernel: blockwise online-softmax (flash) attention.

dOS structure, applied to attention: the KV sequence is the contraction
dimension. KV blocks play the "tiers" (innermost sequential grid dim);
the output tile (bq x D), the running max m and the running normalizer l
stay **stationary in VMEM** across KV steps — the attention analogue of
the paper's stationary partial-sum pile, with the softmax rescaling as
the tier-to-tier accumulation rule.

Supports causal masking, sliding-window (local) masking, GQA head
grouping and cross-attention (no mask), so it serves every attention
flavour in the model zoo (gemma3 local:global, whisper cross-attn,
llama vision cross-attn, ...). The window is a scalar prefetched into
SMEM, so a scanned layer stack may pass each layer's window traced.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._mxu import mxu_precision
from .ref import NEG_INF

__all__ = ["flash_attention_pallas"]

_LANES = 128  # TPU vector lane width for the m/l scratch
#: window used when none is given: wider than any sequence (global attn)
_NO_WINDOW = 2**30


def _attn_kernel(
    win_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    acc_ref,
    m_ref,
    l_ref,
    *,
    n_kv: int,
    bq: int,
    bk: int,
    causal: bool,
    kv_len: int | None,
    scale: float,
    q_offset: int,
    out_dtype,
):
    kv_step = pl.program_id(2)

    @pl.when(kv_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Operands enter the MXU in their own dtype; accumulation is f32.
    q = q_ref[0]  # (bq, d)
    prec = mxu_precision(q.dtype)
    s = jax.lax.dot_general(
        q, k_ref[0], (((1,), (1,)), ((), ())),  # q @ k.T
        preferred_element_type=jnp.float32, precision=prec,
    ) * scale  # (bq, bk)

    q_idx = pl.program_id(1) * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    q_idx = q_idx + q_offset
    k_idx = kv_step * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_idx > q_idx - win_ref[0]
    if causal:
        mask = mask & (k_idx <= q_idx)
    if kv_len is not None:  # keys padded up to a block multiple
        mask = mask & (k_idx < kv_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]  # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)  # (bq, bk)
    corr = jnp.exp(m_prev - m_new)  # (bq, 1)

    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0]  # (bk, d)
    acc_ref[...] = acc_ref[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32, precision=prec
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(kv_step == n_kv - 1)
    def _emit():
        l = l_ref[:, :1]
        l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows stay zero
        o_ref[0, ...] = (acc_ref[...] / l).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "scale", "q_offset", "bq", "bk", "group", "heads", "kv_len",
        "interpret",
    ),
)
def flash_attention_pallas(
    q: jax.Array,  # (BH, Sq, D)   flattened batch*heads
    k: jax.Array,  # (BKVH, Skv, D)
    v: jax.Array,
    window=None,  # None, an int or a traced int32 scalar (SMEM-prefetched)
    *,
    group: int,  # q heads per kv head (GQA)
    heads: int | None = None,  # q heads per batch (for kv index math)
    causal: bool = True,
    scale: float | None = None,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    kv_len: int | None = None,  # keys at or past kv_len are padding
    interpret: bool = False,
) -> jax.Array:
    bh, sq, d = q.shape
    bkvh, skv, _ = k.shape
    h = heads if heads is not None else bh  # q heads per batch row
    kvh = h // group
    assert sq % bq == 0 and skv % bk == 0, (sq, bq, skv, bk)
    if scale is None:
        scale = 1.0 / (d**0.5)
    win = jnp.reshape(
        jnp.asarray(_NO_WINDOW if window is None else window, jnp.int32), (1,)
    )
    n_kv = skv // bk
    grid = (bh, sq // bq, n_kv)

    def q_map(bhi, i, j, win_ref):
        return (bhi, i, 0)

    def kv_map(bhi, i, j, win_ref):
        b = bhi // h
        hh = bhi % h
        return (b * kvh + hh // group, j, 0)

    kernel = functools.partial(
        _attn_kernel,
        n_kv=n_kv,
        bq=bq,
        bk=bk,
        causal=causal,
        kv_len=kv_len,
        scale=scale,
        q_offset=q_offset,
        out_dtype=q.dtype,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, bq, d), q_map),
                pl.BlockSpec((1, bk, d), kv_map),
                pl.BlockSpec((1, bk, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, bq, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
                pltpu.VMEM((bq, _LANES), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(win, q, k, v)
