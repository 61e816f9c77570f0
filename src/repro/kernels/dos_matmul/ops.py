"""Public op: dOS matmul with padding, block selection and CPU fallback.

``dos_matmul`` is the layer-facing entry point used by the model zoo.
On TPU it calls the Pallas kernel; on CPU (this container) it uses the
pure-jnp reference so smoke tests lower plain XLA HLO.
``interpret=True`` forces the Pallas kernel in interpret mode (used by
the kernel test-suite to validate the kernel body on CPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import dos_matmul_pallas
from .ref import dos_matmul_ref, matmul_ref

__all__ = ["dos_matmul", "pick_blocks"]


# Minimum Pallas tile (sublane, lane) for f32; shapes below this are
# dominated by zero padding and dispatch to the reference GEMM instead.
MIN_TILE_M = 8
MIN_TILE_N = 128
MIN_TILE_K = 128


def pick_blocks(m: int, n: int, k: int, vmem_budget_bytes: int = 8 * 2**20):
    """MXU-aligned block sizes fitting the VMEM budget.

    Working set (bf16 operands + f32 acc): 2(bm*bk + bk*bn) + 4*bm*bn.
    Prefers 128-aligned bm/bn and a deep K block (dOS wants as much of
    the contraction resident as possible: fewer "tier" iterations).
    Skewed (tall/wide) GEMMs get rectangular tiles: when one output dim
    is small, its freed VMEM goes to the other dim — fewer grid rows
    and better reuse of the small operand — instead of sitting idle.
    """

    def fits(bm_, bn_, bk_):
        return 2 * (bm_ * bk_ + bk_ * bn_) + 4 * bm_ * bn_ <= vmem_budget_bytes

    bm = min(128, _round_up(m, MIN_TILE_M))
    bn = min(128, _round_up(n, MIN_TILE_N))
    if n <= 128 < m:  # tall: grow bm while the min-depth K block fits
        while bm < 512 and bm < _round_up(m, MIN_TILE_M) and fits(2 * bm, bn, MIN_TILE_K):
            bm *= 2
        bm = min(bm, _round_up(m, MIN_TILE_M))
    elif m <= 128 < n:  # wide: grow bn symmetrically
        while bn < 512 and bn < _round_up(n, MIN_TILE_N) and fits(bm, 2 * bn, MIN_TILE_K):
            bn *= 2
        bn = min(bn, _round_up(n, MIN_TILE_N))
    bk = 512
    while not fits(bm, bn, bk) and bk > MIN_TILE_K:
        bk //= 2
    return bm, bn, min(bk, _round_up(k, MIN_TILE_K))


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "blocks", "interpret", "force_ref")
)
def dos_matmul(
    a: jax.Array,
    b: jax.Array,
    *,
    out_dtype=None,
    blocks: tuple | None = None,
    interpret: bool | None = None,
    force_ref: bool = False,
) -> jax.Array:
    """``a(..., M, K) @ b(K, N)`` via the dOS Pallas kernel.

    Leading batch dims of ``a`` are flattened into M. Inputs are padded
    up to block multiples and the result is sliced back.

    Dispatch: on TPU -> Pallas kernel; on CPU -> jnp reference (so smoke
    tests lower plain XLA HLO). Pass ``interpret=True`` to force the
    kernel body in interpret mode (kernel test-suite).
    """
    out_dtype = out_dtype or a.dtype
    if interpret is None:
        if force_ref or jax.default_backend() != "tpu":
            return matmul_ref(a, b, out_dtype)
        interpret = False
    elif force_ref:
        return matmul_ref(a, b, out_dtype)

    lead = a.shape[:-1]
    m = 1
    for d in lead:
        m *= d
    k = a.shape[-1]
    n = b.shape[-1]

    # Degenerate shapes (any dim below the minimum tile): the padded
    # kernel would spend most of its FLOPs on zeros — use the reference
    # GEMM, which XLA handles without padding waste.
    if m < MIN_TILE_M or n < MIN_TILE_N or k < MIN_TILE_K:
        return matmul_ref(a, b, out_dtype)

    a2 = a.reshape(m, k)

    bm, bn, bk = blocks or pick_blocks(m, n, k)
    mp, np_, kp = _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)
    if (mp, kp) != (m, k):
        a2 = jnp.pad(a2, ((0, mp - m), (0, kp - k)))
    b2 = b
    if (kp, np_) != (k, n):
        b2 = jnp.pad(b, ((0, kp - k), (0, np_ - n)))
    out = dos_matmul_pallas(
        a2, b2, bm=bm, bn=bn, bk=bk, out_dtype=out_dtype, interpret=interpret
    )
    return out[:m, :n].reshape(*lead, n)
