"""Public attention ops used by the model zoo.

``flash_attention``: training/prefill attention over full sequences.
On TPU it dispatches to the Pallas kernel; on CPU to the jnp reference
(clean HLO for smoke tests and the multi-pod dry-run).

``decode_attention``: single-token attention against a KV cache. This
is a bandwidth-bound matvec (no flash tiling needed); implemented as
einsum so XLA shards it freely across the mesh.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...parallel.axes import shard
from .chunked import flash_core
from .kernel import flash_attention_pallas
from .ref import attention_ref

__all__ = ["flash_attention", "decode_attention", "flash_attention_jnp"]


def flash_attention_jnp(q, k, v, *, causal=True, window=None, scale=None,
                        q_offset=0, chunk=512, unroll=False):
    """Chunked flash attention (custom-VJP lax.scan) on (B,S,H,D)
    layouts — the CPU/dry-run path with kernel-equivalent memory
    behaviour. ``window`` may be a traced scalar."""
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    g = h // kvh
    scale = scale if scale is not None else 1.0 / (d**0.5)
    # grouped GQA layout: q (B,KVH,G,Sq,D), k/v (B,KVH,Skv,D) — the core
    # contracts each KV head against its G query heads directly instead
    # of materializing g× repeated K/V copies.
    qt = q.reshape(b, sq, kvh, g, d).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    win = jnp.float32(jnp.inf) if window is None else jnp.asarray(window, jnp.float32)
    chunk = min(chunk, skv)
    o = flash_core(qt, kt, vt, win, causal, float(scale), int(q_offset), chunk, unroll)
    return o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, KVH, D)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_offset: int = 0,
    interpret: bool | None = None,
    force_ref: bool = False,
    unroll: bool = False,
) -> jax.Array:
    """Dispatching wrapper (plain function — the surrounding model jit
    traces it; keeping it un-jitted preserves python ints as static
    tiling params for the Pallas path)."""
    if force_ref:
        return attention_ref(
            q, k, v, causal=causal, window=window, scale=scale, q_offset=q_offset
        )
    if interpret is None:
        if jax.default_backend() != "tpu":
            return flash_attention_jnp(
                q, k, v, causal=causal, window=window, scale=scale,
                q_offset=q_offset, unroll=unroll,
            )
        interpret = False

    # Pallas path: tiling parameters must be static Python values (the
    # window may be traced: the kernel reads it from SMEM).
    assert q_offset is None or isinstance(q_offset, int)
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    group = h // kvh
    bq = min(128, sq)
    bk = min(128, skv)
    # Pad both sequences up to block multiples: padded queries are
    # sliced away, padded keys are masked by kv_len.
    sqp, skvp = -(-sq // bq) * bq, -(-skv // bk) * bk
    if sqp != sq:
        q = jnp.pad(q, ((0, 0), (0, sqp - sq), (0, 0), (0, 0)))
    if skvp != skv:
        k, v = (jnp.pad(x, ((0, 0), (0, skvp - skv), (0, 0), (0, 0))) for x in (k, v))
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sqp, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * kvh, skvp, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * kvh, skvp, d)
    o = flash_attention_pallas(
        qf,
        kf,
        vf,
        window,
        group=group,
        heads=h,
        causal=causal,
        scale=scale,
        q_offset=q_offset,
        bq=bq,
        bk=bk,
        kv_len=skv if skvp != skv else None,
        interpret=interpret,
    )
    return o.reshape(b, h, sqp, d)[:, :, :sq].transpose(0, 2, 1, 3)


def decode_attention(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, KVH, D)
    v_cache: jax.Array,
    *,
    length: jax.Array | int,  # valid cache length (scalar or per-batch)
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """One decode step: q attends to the first ``length`` cache slots
    (and at most the trailing ``window`` of them, if sliding)."""
    b, _, h, d = q.shape
    _, s, kvh, _ = k_cache.shape
    g = h // kvh
    scale = scale if scale is not None else 1.0 / (d**0.5)

    qf = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qf = qf.reshape(b, kvh, g, d)
    # match the cache layout (KVH-sharded when divisible) so the logits
    # einsum partitions by head instead of all-gathering the cache.
    qf = shard(qf, "decode_q_kvh")
    # Transpose the cache to (B, KVH, S, D) — in its storage dtype, so
    # no f32 second copy is materialized (f32 accumulation comes from
    # preferred_element_type). With KVH leading, both contractions lower
    # as plain batched GEMV over S instead of a strided 5-D einsum with
    # a dummy q axis, which is markedly faster on CPU.
    kc = k_cache.transpose(0, 2, 1, 3)
    vc = v_cache.transpose(0, 2, 1, 3)
    logits = jnp.einsum(
        "bkgd,bksd->bkgs", qf, kc,
        preferred_element_type=jnp.float32,
    )  # (b, kvh, g, s)

    pos = jnp.arange(s)
    lengths = jnp.broadcast_to(jnp.asarray(length), (b,))[:, None]
    valid = pos[None, :] < lengths
    if window is not None:
        # window includes the newest position (index length-1)
        valid = valid & (pos[None, :] >= lengths - window)
    neg = jnp.finfo(jnp.float32).min * 0.7
    vmask = valid[:, None, None, :]
    logits = jnp.where(vmask, logits, neg)
    m = jnp.max(logits, axis=-1, keepdims=True)
    # Zero the masked slots explicitly: when NO slot is valid (length=0,
    # or a window that excludes everything) the max trick would yield a
    # uniform softmax over garbage — the output must be exact zeros.
    p = jnp.where(vmask, jnp.exp(logits - m), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(denom > 0.0, denom, 1.0)
    o = jnp.einsum(
        "bkgs,bksd->bkgd", p.astype(v_cache.dtype), vc,
        preferred_element_type=jnp.float32,
    )
    return o.reshape(b, 1, h, d).astype(q.dtype)
