"""Chunked flash attention in pure jnp with a flash-2 custom VJP.

This is the CPU twin of the Pallas kernel: the same online-softmax
chunking (KV blocks = sequential "tiers", output/m/l stationary)
expressed as a ``lax.scan`` so the lowered HLO has O(S*d) residency,
as the kernel has, instead of a naive S x S materialization.

Forward saves only (q, k, v, o, lse); the backward recomputes p per
block (flash-2):

    D_i  = rowsum(dO * O)
    p_ij = exp(q_i k_j^T * scale - lse_i)
    dV_j = p^T dO
    dS   = p * (dO V_j^T - D_i) * scale
    dQ_i += dS K_j ;  dK_j += dS^T Q_i

Layout is grouped for GQA: q is (B, KVH, G, Sq, D) and k/v are
(B, KVH, Skv, D), so each KV head is contracted against its G query
heads directly inside the einsums — no ``jnp.repeat`` materializing g×
copies of K/V per chunk. The backward's dK/dV einsums sum over G, which
is exactly the group-gradient reduction the repeat VJP used to do.
``window`` is a traced f32 scalar (+inf = global) so per-layer scanned
metadata works; its cotangent is zero.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ref import NEG_INF

__all__ = ["flash_core"]


def _mask(qi, kj, causal: bool, window):
    qi_ = qi[:, None]
    kj_ = kj[None, :]
    ok = jnp.ones((qi.shape[0], kj.shape[0]), bool)
    if causal:
        ok = ok & (kj_ <= qi_)
    ok = ok & (kj_ > qi_ - window)
    return ok


def _chunk_kv(k, chunk):
    """(B,KVH,Skv,D) -> (nkv, B,KVH,chunk,D) with zero tail padding."""
    b, kvh, skv, d = k.shape
    nkv = -(-skv // chunk)
    pad = nkv * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return k.reshape(b, kvh, nkv, chunk, d).transpose(2, 0, 1, 3, 4)


def _fwd_impl(q, k, v, window, causal, scale, q_offset, chunk, unroll=False):
    b, kvh, g, sq, d = q.shape
    skv = k.shape[2]
    kc = _chunk_kv(k, chunk)
    vc = _chunk_kv(v, chunk)

    qf = q.astype(jnp.float32) * scale
    qi = jnp.arange(sq) + q_offset

    def step(carry, inp):
        m, l, acc, j = carry
        k_j, v_j = inp
        s = jnp.einsum("bkgqd,bkcd->bkgqc", qf, k_j.astype(jnp.float32))
        kj = j * chunk + jnp.arange(chunk)
        ok = _mask(qi, kj, causal, window) & (kj < skv)[None, :]
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgqc,bkcd->bkgqd", p, v_j.astype(jnp.float32)
        )
        return (m_new, l, acc, j + 1), None

    # init carries derived from q so their varying-axes match inside
    # shard_map bodies (constants would be non-varying and scan would
    # reject them there).
    zq = jnp.zeros_like(qf)
    m0 = zq[..., 0] + NEG_INF
    l0 = zq[..., 0]
    a0 = zq
    (m, l, acc, _), _ = jax.lax.scan(step, (m0, l0, a0, jnp.int32(0)), (kc, vc), unroll=unroll)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l_safe[..., None]).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_core(q, k, v, window, causal: bool, scale: float, q_offset: int,
               chunk: int, unroll: bool = False):
    """q: (B,KVH,G,Sq,D); k, v: (B,KVH,Skv,D); window: f32 scalar
    (inf=global). Returns o: (B,KVH,G,Sq,D)."""
    o, _ = _fwd_impl(q, k, v, window, causal, scale, q_offset, chunk, unroll)
    return o


def _fwd_rule(q, k, v, window, causal, scale, q_offset, chunk, unroll=False):
    o, lse = _fwd_impl(q, k, v, window, causal, scale, q_offset, chunk, unroll)
    return o, (q, k, v, window, o, lse)


def _bwd_rule(causal, scale, q_offset, chunk, unroll, res, do):
    q, k, v, window, o, lse = res
    b, kvh, g, sq, d = q.shape
    skv = k.shape[2]
    nkv = -(-skv // chunk)
    kc = _chunk_kv(k, chunk)
    vc = _chunk_kv(v, chunk)

    qf = q.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    qi = jnp.arange(sq) + q_offset
    D = jnp.sum(dof * o.astype(jnp.float32), axis=-1)  # (b,kvh,g,sq)

    def step(dq, inp):
        k_j, v_j, j = inp
        kjf = k_j.astype(jnp.float32)
        s = jnp.einsum("bkgqd,bkcd->bkgqc", qf * scale, kjf)
        kj = j * chunk + jnp.arange(chunk)
        ok = _mask(qi, kj, causal, window) & (kj < skv)[None, :]
        s = jnp.where(ok[None, None, None], s, NEG_INF)
        p = jnp.exp(s - lse[..., None])  # (b,kvh,g,q,c)
        # dV/dK contract over g as well: the per-group gradient sum that
        # jnp.repeat's VJP used to perform.
        dv_j = jnp.einsum("bkgqc,bkgqd->bkcd", p, dof)
        dp = jnp.einsum("bkgqd,bkcd->bkgqc", dof, v_j.astype(jnp.float32))
        ds = p * (dp - D[..., None]) * scale
        dq = dq + jnp.einsum("bkgqc,bkcd->bkgqd", ds, kjf)
        dk_j = jnp.einsum("bkgqc,bkgqd->bkcd", ds, qf)
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros_like(qf)
    js = jnp.arange(nkv, dtype=jnp.int32)
    dq, (dks, dvs) = jax.lax.scan(step, dq0, (kc, vc, js), unroll=unroll)
    dk = dks.transpose(1, 2, 0, 3, 4).reshape(b, kvh, nkv * chunk, d)[:, :, :skv]
    dv = dvs.transpose(1, 2, 0, 3, 4).reshape(b, kvh, nkv * chunk, d)[:, :, :skv]
    return (
        dq.astype(q.dtype),
        dk.astype(k.dtype),
        dv.astype(v.dtype),
        jnp.zeros_like(window),
    )


flash_core.defvjp(_fwd_rule, _bwd_rule)
