"""Pallas TPU kernel: chunked SSD (Mamba2 / mLSTM) scan.

dOS structure applied to a recurrence: time is the contraction
dimension. The sequence is tiled into chunks (the innermost sequential
grid dim — the "tiers"); the inter-chunk SSM state (N x P) stays
**stationary in a VMEM f32 scratch** across chunk steps, exactly like
the dOS partial-sum pile. Within a chunk, the recurrence is rewritten
as dense MXU matmuls (the SSD "matrix transform" form):

  per chunk of length T, with la_i = cumsum(ld_i) (log-decay, summed
  in XLA before the kernel):
    L_ij    = exp(la_i - la_j)  for j <= i else 0     (T x T)
    y_intra = ((C B^T) * L) @ U                        (T x P)
    y_inter = exp(la_i) * (C_i @ S_prev)               (T x P)
    S_new   = exp(la_T) S_prev + (exp(la_T - la_j) B_j)^T @ U

All accumulation in f32. Grid: (batch*heads, n_chunks); the chunk dim
is sequential ('arbitrary') so the state scratch carries across chunks
of the same (b, h) row. The final state is emitted as a second output
(prefill hands it to the decode loop).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._mxu import mxu_precision

__all__ = ["ssm_scan_pallas"]


def _ssd_kernel(u_ref, lac_ref, lar_ref, b_ref, c_ref, y_ref, sout_ref, s_ref, *,
                chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    u = u_ref[0].astype(jnp.float32)  # (T, P)
    la_c = lac_ref[0]  # (T, 1) log cumulative decay, as a column
    la_r = lar_ref[0, 0]  # (1, T) the same values, as a row
    bmat = b_ref[0].astype(jnp.float32)  # (T, N)
    cmat = c_ref[0].astype(jnp.float32)  # (T, N)
    f32 = dict(
        preferred_element_type=jnp.float32, precision=mxu_precision(jnp.float32)
    )

    # Intra-chunk: ((C B^T) * L) @ U with L the decay-masked lower tri.
    cb = jnp.dot(cmat, bmat.T, **f32)  # (T, T)
    li = la_c - la_r  # la_i - la_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lmat = jnp.exp(jnp.where(jj <= ii, li, -1e30))  # mask before exp
    y = jnp.dot(cb * lmat, u, **f32)  # (T, P)

    # Inter-chunk: previous state decayed to each position.
    s_prev = s_ref[...]  # (N, P)
    y = y + jnp.exp(la_c) * jnp.dot(cmat, s_prev, **f32)

    # State update for the next chunk.
    la_last = la_r[:, chunk - 1 :]  # (1, 1)
    bdec = bmat * jnp.exp(la_last - la_c)  # (T, N)
    s_new = jnp.exp(la_last) * s_prev + jax.lax.dot_general(
        bdec, u, (((0,), (0,)), ((), ())), **f32
    )  # (N, P)
    s_ref[...] = s_new

    y_ref[0, ...] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        sout_ref[0, ...] = s_new


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssm_scan_pallas(
    u: jax.Array,  # (BH, S, P) flattened batch*heads
    ld: jax.Array,  # (BH, S, 1) log-decay
    B: jax.Array,  # (BH, S, N)
    C: jax.Array,  # (BH, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (y: (BH, S, P), final_state: (BH, N, P) f32)."""
    bh, s, p = u.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    n_chunks = s // chunk
    grid = (bh, n_chunks)

    def seq_map(i, j):
        return (i, j, 0)

    def row_map(i, j):
        return (i, 0, 0)

    def chunk_row_map(i, j):
        return (i, j, 0, 0)

    # The per-chunk prefix sum of the log-decay is taken here, in XLA:
    # Mosaic has no cumsum. The kernel reads it as a column and as one
    # (1, chunk) row per chunk (a block equal to its array's last dims).
    la = jnp.cumsum(
        ld.astype(jnp.float32).reshape(bh, n_chunks, 1, chunk), axis=-1
    )
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), seq_map),
            pl.BlockSpec((1, chunk, 1), seq_map),
            pl.BlockSpec((1, 1, 1, chunk), chunk_row_map),
            pl.BlockSpec((1, chunk, n), seq_map),
            pl.BlockSpec((1, chunk, n), seq_map),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), seq_map),
            pl.BlockSpec((1, n, p), row_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), u.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(u, la.reshape(bh, s, 1), la, B, C)
