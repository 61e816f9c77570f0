"""JAX set-up shared by the repo's entry points.

Written for jax 0.9.0. Two things live here:

- ``make_mesh``: jax 0.9's ``jax.make_mesh`` defaults to Explicit axis
  types; the sharded design-space search (``parallel.shard_eval``) and
  the tests build their meshes here with Auto axes, whose shardings the
  compiler propagates.
- ``use_compile_cache``: where JAX's persistent compilation cache
  lives. Entry points (``python -m repro``, ``chip_smoke.py``, the
  benchmark scripts) call it once at start-up; importing this module
  changes nothing.
"""

from __future__ import annotations

import os
import pathlib

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "use_compile_cache", "REPO_COMPILE_CACHE"]

#: The cache directory when ``JAX_COMPILATION_CACHE_DIR`` is unset:
#: fixed at the repository root, since a cache whose path moves between
#: runs never hits.
REPO_COMPILE_CACHE = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins: JAX reads it itself, and
    nothing else is configured. Otherwise the cache goes to
    ``REPO_COMPILE_CACHE``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_COMPILE_CACHE))
    return str(REPO_COMPILE_CACHE)
