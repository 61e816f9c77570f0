"""MXU contraction precision shared by the Pallas kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def mxu_precision(dtype):
    """Contraction precision for operands of ``dtype`` inside a kernel.

    Mosaic contracts f32 operands in one bf16 pass unless told
    otherwise, which rounds them to bf16 (on a TPU v5e the f32 flash
    kernel then missed its reference by 9e-3). f32 operands therefore
    ask for the multi-pass fp32 contraction; bf16 operands are exact in
    one pass and keep the default.
    """
    return jax.lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32 else None
