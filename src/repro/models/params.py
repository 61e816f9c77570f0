"""Parameter definition trees.

A model is described by a pytree of ``ParamDef`` leaves (shape, GEMM
contraction axis index, initializer). From one tree we derive real
parameters (``materialize``) and ShapeDtypeStructs for tracing
(``abstract``).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

__all__ = ["ParamDef", "materialize", "abstract", "tree_size", "stack_defs"]


@dataclasses.dataclass(frozen=True)
class ParamDef:
    shape: tuple
    contract: int | None = None  # GEMM contraction axis index (the fan-in)
    init: str = "normal"  # normal | zeros | ones | neg_linspace
    scale: float | None = None  # None -> 1/sqrt(fan_in)


def _is_def(x):
    return isinstance(x, ParamDef)


def materialize(defs, rng: jax.Array, dtype=jnp.float32):
    """Initialize real parameters for a ParamDef tree."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=_is_def)
    rngs = jax.random.split(rng, len(leaves))

    def one(d: ParamDef, key):
        if d.init == "zeros":
            return jnp.zeros(d.shape, dtype)
        if d.init == "ones":
            return jnp.ones(d.shape, dtype)
        if d.init == "neg_linspace":  # mamba A: -[1..H], broadcast over stacking
            h = d.shape[-1]
            v = -jnp.linspace(1.0, float(h), h).astype(dtype)
            return jnp.broadcast_to(v, d.shape)
        fan_in = d.shape[d.contract] if d.contract is not None else d.shape[0]
        scale = d.scale if d.scale is not None else 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, d.shape) * scale).astype(dtype)

    return jax.tree.unflatten(treedef, [one(d, k) for d, k in zip(leaves, rngs)])


def abstract(defs, dtype=jnp.float32):
    """ShapeDtypeStruct tree (no allocation) for .lower()."""
    return jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs, is_leaf=_is_def
    )


def tree_size(defs) -> int:
    """Total parameter count of a ParamDef tree."""
    return sum(
        math.prod(d.shape) for d in jax.tree.leaves(defs, is_leaf=_is_def)
    )


def stack_defs(defs, n: int):
    """Prepend a stacked-layers dimension to every leaf (for lax.scan)."""

    def one(d: ParamDef):
        return ParamDef(
            shape=(n,) + d.shape,
            contract=None if d.contract is None else d.contract + 1,
            init=d.init,
            scale=d.scale,
        )

    return jax.tree.map(one, defs, is_leaf=_is_def)
