"""Device-sharded dispatch of the batched (R, C) design-space search.

The engine's hot kernel (``core.analytical._search_rc``) is rowwise
independent: every design point's search reads only its own
(D1, D2, Tser, budget) row. That makes data-parallel execution across
the host's JAX devices exact — this module splits the flat point batch
over a 1-D device mesh with ``shard_map`` and runs the *same* jitted
kernel per shard, so sharded and unsharded results are bit-for-bit
identical (regression-pinned by ``tests/test_scale.py``).

On a plain CPU host there is one device and ``shard='auto'`` degrades
to the single-device path; multi-device CPU testing uses
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (see
``tests/conftest.run_multidevice``).
"""

from __future__ import annotations

import functools

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["resolve_shards", "sharded_search"]


def resolve_shards(shard) -> int:
    """Normalize an ``evaluate(shard=...)`` request to a shard count.

    ``None``/``'none'``/``1`` -> 1 (unsharded). ``'auto'`` -> the number
    of local JAX devices. An explicit int must not exceed the local
    device count (``shard_map`` places one sub-batch per device).
    """
    if shard is None or shard == "none" or shard == 1:
        return 1
    import jax

    n_dev = jax.local_device_count()
    if shard == "auto":
        return max(n_dev, 1)
    try:
        n = int(shard)
    except (TypeError, ValueError):
        raise ValueError(
            f"shard must be None, 'none', 'auto' or a positive int, got {shard!r}"
        ) from None
    if n < 1:
        raise ValueError(f"shard must be >= 1, got {n}")
    if n > n_dev:
        raise ValueError(
            f"shard={n} exceeds the {n_dev} local JAX device(s); "
            "set XLA_FLAGS=--xla_force_host_platform_device_count for CPU testing"
        )
    return n


@functools.lru_cache(maxsize=32)
def _sharded_search_fn(n_shards: int, r_max_total: int):
    """jit(shard_map(_search_rc)) over a 1-D ('shard',) device mesh.

    Cached per (shard count, static search width) like the engine's
    single-device ``_jax_search_fn`` — one compile per width class.
    Like it, returns r, c and tau stacked as one (3, rows) int64 array,
    sharded along the rows.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from .._jax_compat import make_mesh
    from ..core.analytical import _search_rc

    mesh = make_mesh((n_shards,), ("shard",))

    def sharded_search_rc(D1, D2, Tser, budget):
        return jnp.stack(_search_rc(jnp, D1, D2, Tser, budget, r_max_total))

    fn = jax.shard_map(
        sharded_search_rc,
        mesh=mesh,
        in_specs=(P("shard"),) * 4,
        out_specs=P(None, "shard"),
    )
    return jax.jit(fn)


def sharded_search(D1, D2, Tser, budget, r_max_total: int, n_shards: int):
    """Run one search batch split across ``n_shards`` devices.

    Inputs are (B,) int64 numpy arrays; B need not divide the shard
    count — the batch is padded with trivial rows (all-ones searches)
    and sliced back, so degenerate batches (B < n_shards, B == 1) are
    exact. Caller is expected to hold jax's ``enable_x64`` scope, like
    the engine's unsharded jax path. The chips' stacked (3, rows)
    results come back in one device->host copy (span
    ``repro.search.fetch``, ``copies`` 1); returns (r, c, tau), three
    (B,) int64 numpy arrays.
    """
    B = D1.shape[0]
    pad = (-B) % n_shards
    if pad:
        one = np.ones(pad, dtype=np.int64)
        D1, D2, Tser, budget = (
            np.concatenate([a, one]) for a in (D1, D2, Tser, budget)
        )
    fn = _sharded_search_fn(n_shards, r_max_total)
    out = fn(D1, D2, Tser, budget)
    with TraceAnnotation("repro.search.fetch", rows=B, copies=1):
        r, c, t = np.asarray(out)[:, :B]
    return r, c, t
