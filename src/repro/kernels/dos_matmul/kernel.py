"""Pallas TPU kernel: dOS (distributed-output-stationary) tiled matmul.

The paper's dOS dataflow adapted to the TPU memory hierarchy:

- The MXU plays the role of one 2D systolic tier (it literally is one).
- The contraction dimension K is tiled across the **pallas grid's
  innermost (sequential) dimension** — K-blocks are the "tiers",
  executed temporally on one chip, exactly like Eq. 2's K/ℓ slices.
- The output tile stays **stationary in a VMEM f32 scratch accumulator**
  across all K-steps (the "output stationary" part); partial sums are
  accumulated in-register/VMEM instead of over TSVs.

Block shapes are chosen MXU-aligned (multiples of 128 in M/N, K-block a
multiple of the dtype's packing); the VMEM working set is
bm*bk + bk*bn (operands) + bm*bn (f32 acc) elements.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .._mxu import mxu_precision

__all__ = ["dos_matmul_kernel", "dos_matmul_pallas"]


def dos_matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_k_tiers: int, out_dtype):
    """One (i, j, k) grid step: accumulate a K-tier into the stationary
    output tile; emit on the last tier."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        a_ref[...], b_ref[...], preferred_element_type=jnp.float32,
        precision=mxu_precision(a_ref.dtype),
    )

    @pl.when(k == n_k_tiers - 1)
    def _emit():
        o_ref[...] = acc_ref[...].astype(out_dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "out_dtype", "interpret")
)
def dos_matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    bm: int = 128,
    bn: int = 128,
    bk: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """``a(M,K) @ b(K,N)`` with dOS K-tiering. Shapes must divide blocks
    (the ops.py wrapper pads); K-tier count = K // bk."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (
        f"shape ({m},{k})x({k2},{n}) must divide blocks ({bm},{bn},{bk})"
    )
    out_dtype = out_dtype or a.dtype
    n_k = k // bk

    grid = (m // bm, n // bn, n_k)
    kernel = functools.partial(
        dos_matmul_kernel, n_k_tiers=n_k, out_dtype=out_dtype
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a, b)
