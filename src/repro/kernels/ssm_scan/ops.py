"""Public op: chunked SSD scan with CPU fallback.

``ssm_scan(u, ld, B, C)`` with model-facing layout
u: (Bt, S, H, P), ld: (Bt, S, H), B/C: (Bt, S, H, N).
Returns (y: (Bt, S, H, P), final_state: (Bt, H, N, P) f32).

On TPU dispatches to the Pallas chunked kernel (per-(batch, head)
rows); on CPU uses a *chunked jnp implementation with identical math*
(so the lowered HLO has the real matmul structure, not a length-S
scan). The step-by-step reference remains the validation oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import ssm_scan_pallas
from .ref import ssm_scan_ref

__all__ = ["ssm_scan", "ssm_scan_chunked_jnp"]


def ssm_scan_chunked_jnp(u, ld, B, C, chunk: int = 128, unroll: bool = False):
    """Chunked SSD in plain jnp — the same math as the Pallas kernel,
    vectorized over (batch, head); used on CPU."""
    bt, s, h, p = u.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    uf = u.astype(jnp.float32).reshape(bt, nc, chunk, h, p)
    ldf = ld.astype(jnp.float32).reshape(bt, nc, chunk, h)
    Bf = B.astype(jnp.float32).reshape(bt, nc, chunk, h, n)
    Cf = C.astype(jnp.float32).reshape(bt, nc, chunk, h, n)

    la = jnp.cumsum(ldf, axis=2)  # (bt,nc,T,h)

    # Intra-chunk (batched over bt, nc, h).
    cb = jnp.einsum("bcihn,bcjhn->bcijh", Cf, Bf)  # (bt,nc,T,T,h)
    li = la[:, :, :, None, :] - la[:, :, None, :, :]  # (bt,nc,T,T,h)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask BEFORE exp: the j>i entries are positive log-decays whose exp
    # overflows; where() after exp leaks inf*0=NaN into the backward.
    li = jnp.where(tri[None, None, :, :, None], li, -1e30)
    lmat = jnp.exp(li)
    y_intra = jnp.einsum("bcijh,bcjhp->bcihp", cb * lmat, uf)

    # Cross-chunk state: sequential scan over chunks (nc steps). The
    # scan carries ONLY the state — each step is an elementwise
    # decay-and-add on (bt,h,n,p) and emits the state *entering* the
    # chunk; the C-contraction is hoisted out into one batched einsum
    # over all chunks below, cutting per-step dispatch overhead.
    decay_tot = jnp.exp(la[:, :, -1, :])  # (bt,nc,h)
    dec = jnp.exp(la[:, :, -1:, :] - la)  # (bt,nc,T,h)
    s_inc = jnp.einsum("bcjhn,bcjh,bcjhp->bchnp", Bf, dec, uf)

    def chunk_step(state, inp):
        d_tot, inc = inp
        prev = state
        state = d_tot[:, :, None, None] * state + inc
        return state, prev

    inputs = (
        decay_tot.transpose(1, 0, 2),
        s_inc.transpose(1, 0, 2, 3, 4),
    )
    s0 = jnp.zeros((bt, h, n, p), jnp.float32)
    final, prevs = jax.lax.scan(chunk_step, s0, inputs, unroll=unroll)
    states = prevs.transpose(1, 0, 2, 3, 4)  # (bt,nc,h,n,p)
    y_inter = jnp.einsum("bcihn,bchnp,bcih->bcihp", Cf, states, jnp.exp(la))

    y = (y_intra + y_inter).reshape(bt, s, h, p)
    return y.astype(u.dtype), final


@functools.partial(jax.jit, static_argnames=("chunk", "interpret", "force_ref", "unroll"))
def ssm_scan(
    u: jax.Array,
    ld: jax.Array,
    B: jax.Array,
    C: jax.Array,
    *,
    chunk: int | None = None,
    interpret: bool | None = None,
    force_ref: bool = False,
    unroll: bool = False,
):
    """Chunked SSD scan; returns (y (Bt,S,H,P), state (Bt,H,N,P)).

    ``chunk=None`` auto-picks: 128 on TPU (MXU-sized tiles for the
    Pallas kernel) but 32 on CPU, where the O(S*T) intra-chunk T×T
    decay matrix dominates and smaller chunks win despite more scan
    steps (the batched cross-chunk step keeps scan overhead flat)."""
    if force_ref:
        return ssm_scan_ref(u, ld, B, C)
    if chunk is None:
        chunk = 128 if jax.default_backend() == "tpu" else 32
    s_orig = u.shape[1]
    chunk = min(chunk, s_orig)
    if s_orig % chunk:
        # Pad with identity steps: ld=0 (decay 1), u=0, B=0 leave the
        # state untouched; the padded outputs are sliced away.
        pad = chunk - s_orig % chunk
        padw = ((0, 0), (0, pad), (0, 0), (0, 0))
        u = jnp.pad(u, padw)
        B = jnp.pad(B, padw)
        C = jnp.pad(C, padw)
        ld = jnp.pad(ld, ((0, 0), (0, pad), (0, 0)))
        y, state = ssm_scan(
            u, ld, B, C, chunk=chunk, interpret=interpret, force_ref=force_ref,
            unroll=unroll,
        )
        return y[:, :s_orig], state
    if interpret is None:
        if jax.default_backend() != "tpu":
            return ssm_scan_chunked_jnp(u, ld, B, C, chunk=chunk, unroll=unroll)
        interpret = False

    bt, s, h, p = u.shape
    n = B.shape[-1]
    ur = u.transpose(0, 2, 1, 3).reshape(bt * h, s, p)
    ldr = ld.transpose(0, 2, 1).reshape(bt * h, s, 1)
    Br = B.transpose(0, 2, 1, 3).reshape(bt * h, s, n)
    Cr = C.transpose(0, 2, 1, 3).reshape(bt * h, s, n)
    y, state = ssm_scan_pallas(ur, ldr, Br, Cr, chunk=chunk, interpret=interpret)
    return (
        y.reshape(bt, h, s, p).transpose(0, 2, 1, 3),
        state.reshape(bt, h, n, p),
    )
