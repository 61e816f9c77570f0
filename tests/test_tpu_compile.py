"""Deviceless compiles for one TPU v5e chip at real widths.

The TPU compiler is installed even where no TPU is attached. Each test
compiles one program of the main path for a *described* v5e chip, which
catches what interpret mode cannot — block tiling, VMEM limits, Mosaic
lowering gaps, programs too large for the chip's 16 GB — while running
nothing. A compile that passes is not a chip run.

The topology is described only inside the module fixture: only one
process at a time may load the TPU library, so doing it at import would
break every other test worker.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.engine import _jax_search_fn
from repro.kernels.dos_matmul import dos_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssm_scan import ssm_scan

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 slice, with the persistent
    compilation cache off (a deviceless compile cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()
    if log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = log_dir


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), "no Mosaic kernel in program"
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES


@pytest.mark.parametrize("m,k,n", [
    (8, 576, 1536),  # decode: batch-8 tokens through smollm's MLP
    (4096, 2048, 8192),
], ids=["decode_m8", "gemm_4096x2048x8192"])
def test_dos_matmul_compiles_for_v5e(one_chip, m, k, n):
    a = jax.ShapeDtypeStruct((m, k), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((k, n), jnp.bfloat16, sharding=one_chip)
    _assert_kernel(_compile(lambda a, b: dos_matmul(a, b, interpret=False), a, b))


@pytest.mark.parametrize("b,s,h,kvh,d", [
    (4, 128, 9, 3, 64),  # smollm-135m prefill, GQA 3:1
    (1, 2048, 16, 2, 128),  # qwen2.5-3b heads, GQA 8:1
    (1, 200, 16, 2, 128),  # a length that is no block multiple
], ids=["d64_gqa3", "d128_gqa8", "d128_ragged"])
def test_flash_attention_compiles_for_v5e(one_chip, b, s, h, kvh, d):
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, kvh, d), jnp.bfloat16, sharding=one_chip)
    win = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)

    def f(q, k, v, w):  # a scanned layer stack passes its window traced
        return flash_attention(q, k, v, causal=True, window=w, interpret=False)

    _assert_kernel(_compile(f, q, kv, kv, win))


def test_ssm_scan_compiles_for_v5e(one_chip):
    bt, s, h, p, n = 2, 1024, 8, 64, 128
    u = jax.ShapeDtypeStruct((bt, s, h, p), jnp.float32, sharding=one_chip)
    ld = jax.ShapeDtypeStruct((bt, s, h), jnp.float32, sharding=one_chip)
    bc = jax.ShapeDtypeStruct((bt, s, h, n), jnp.float32, sharding=one_chip)

    def f(u, ld, B, C):
        return ssm_scan(u, ld, B, C, chunk=128, interpret=False)

    _assert_kernel(_compile(f, u, ld, bc, bc))


def test_search_rc_compiles_for_v5e(one_chip):
    """The engine's jitted (R, C) search at its widest static width and
    64 rows, twice the 2**23 // width rows a launch holds there: int64,
    which the chip emulates, must still fit the chip."""
    rows = jax.ShapeDtypeStruct((64,), jnp.int64, sharding=one_chip)
    with jax.enable_x64(True):
        compiled = _jax_search_fn(1 << 18).lower(rows, rows, rows, rows).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES
