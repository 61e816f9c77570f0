"""Flash attention: Pallas kernel + chunked custom-VJP twin vs oracle."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_ref, decode_attention, flash_attention
from repro.kernels.flash_attention.ops import flash_attention_jnp

CASES = [
    # b, sq, skv, h, kvh, d, causal, window
    (2, 256, 256, 4, 2, 64, True, None),
    (1, 128, 128, 8, 1, 64, True, 128),
    (2, 256, 512, 4, 4, 32, False, None),  # cross
    (1, 384, 384, 2, 2, 128, True, 64),  # sliding window
]


def _mk(b, sq, skv, h, kvh, d, dtype, seed=0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, sq, h, d)), dtype=dtype)
    k = jnp.asarray(rng.normal(size=(b, skv, kvh, d)), dtype=dtype)
    v = jnp.asarray(rng.normal(size=(b, skv, kvh, d)), dtype=dtype)
    return q, k, v


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_pallas_kernel_interpret(case, dtype):
    b, sq, skv, h, kvh, d, causal, window = case
    q, k, v = _mk(b, sq, skv, h, kvh, d, dtype)
    ref = np.asarray(attention_ref(q, k, v, causal=causal, window=window), np.float32)
    out = np.asarray(
        flash_attention(q, k, v, causal=causal, window=window, interpret=True),
        np.float32,
    )
    tol = 3e-2 if dtype == "bfloat16" else 1e-4
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("case", [
    (1, 200, 200, 4, 2, 64, True, None),  # causal, not a block multiple
    (2, 136, 300, 2, 2, 32, False, None),  # cross: padded keys masked
    (1, 200, 200, 2, 2, 64, True, 48),  # sliding window across padding
])
def test_pallas_kernel_interpret_padded_lengths(case):
    b, sq, skv, h, kvh, d, causal, window = case
    q, k, v = _mk(b, sq, skv, h, kvh, d, "float32", seed=4)
    ref = np.asarray(attention_ref(q, k, v, causal=causal, window=window))
    out = np.asarray(
        flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    )
    assert out.shape == ref.shape
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_pallas_kernel_traced_window():
    """Scanned layer stacks pass each layer's window traced; the kernel
    reads it from SMEM and matches the static-window reference."""
    q, k, v = _mk(1, 256, 256, 2, 2, 64, "float32", seed=6)
    run = jax.jit(
        lambda w: flash_attention(q, k, v, causal=True, window=w, interpret=True)
    )
    for w in (64, 2**30):
        ref = attention_ref(q, k, v, causal=True, window=w)
        np.testing.assert_allclose(
            np.asarray(run(jnp.int32(w))), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


@pytest.mark.parametrize("case", CASES)
def test_chunked_jnp_forward_and_grads(case):
    b, sq, skv, h, kvh, d, causal, window = case
    q, k, v = _mk(b, sq, skv, h, kvh, d, "float32", seed=3)
    ref = np.asarray(attention_ref(q, k, v, causal=causal, window=window))
    out = np.asarray(flash_attention_jnp(q, k, v, causal=causal, window=window, chunk=64))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def lc(q, k, v):
        return jnp.sum(flash_attention_jnp(q, k, v, causal=causal, window=window, chunk=64) ** 2)

    def lr(q, k, v):
        return jnp.sum(attention_ref(q, k, v, causal=causal, window=window) ** 2)

    g1 = jax.grad(lc, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-4)


@pytest.mark.parametrize("h,kvh", [(4, 4), (16, 1), (8, 2)])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 3e-2)])
def test_gqa_grouping_extremes(h, kvh, dtype, tol):
    """The grouped-layout core (no jnp.repeat) across the GQA spectrum:
    MHA (h == kvh), MQA (h >> kvh), grouped — per-dtype tolerance
    bands (bf16 rounds the operands, not the algorithm)."""
    q, k, v = _mk(2, 128, 128, h, kvh, 64, dtype, seed=7)
    ref = np.asarray(attention_ref(q, k, v, causal=True), np.float32)
    out = np.asarray(flash_attention_jnp(q, k, v, causal=True, chunk=64), np.float32)
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


def test_decode_single_slot_cache():
    """seq_len=1 KV cache: one valid slot is a deterministic copy of v
    (softmax over one logit), exercising the batched-GEMV path's edge."""
    q, kc, vc = _mk_decode(b=2, s=1, h=4, kvh=2, d=32)
    out = np.asarray(decode_attention(q, kc, vc, length=1))
    want = np.repeat(np.asarray(vc)[:, 0], 2, axis=1).reshape(2, 1, 4, 32)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


def test_decode_bf16_cache_tolerance():
    """bf16 q/cache vs the f32 reference within the bf16 band — the
    restructured path must accumulate logits and o in f32."""
    q, kc, vc = _mk_decode(b=2, s=64, h=4, kvh=2, d=32)
    out32 = np.asarray(decode_attention(q, kc, vc, length=40))
    out16 = np.asarray(
        decode_attention(
            q.astype(jnp.bfloat16), kc.astype(jnp.bfloat16),
            vc.astype(jnp.bfloat16), length=40,
        ),
        np.float32,
    )
    np.testing.assert_allclose(out16, out32, rtol=3e-2, atol=3e-2)


def test_traced_window_matches_static():
    """Per-layer scanned metadata passes window as a traced scalar."""
    q, k, v = _mk(1, 128, 128, 2, 2, 32, "float32", seed=5)
    stat = flash_attention_jnp(q, k, v, causal=True, window=32)
    trac = jax.jit(
        lambda w: flash_attention_jnp(q, k, v, causal=True, window=w)
    )(jnp.int32(32))
    np.testing.assert_allclose(np.asarray(stat), np.asarray(trac), rtol=1e-4, atol=1e-5)


def _mk_decode(b=2, s=64, h=4, kvh=2, d=32, seed=2):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, 1, h, d)), "float32")
    kc = jnp.asarray(rng.normal(size=(b, s, kvh, d)), "float32")
    vc = jnp.asarray(rng.normal(size=(b, s, kvh, d)), "float32")
    return q, kc, vc


def test_decode_matches_ref():
    L = 40
    q, kc, vc = _mk_decode()
    for window in (None, 16):
        ref = attention_ref(q, kc[:, :L], vc[:, :L], causal=True, window=window, q_offset=L - 1)
        out = decode_attention(q, kc, vc, length=L, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_decode_fully_masked_is_zero():
    """length=0 (empty cache) and an everything-excluding window must
    give exact zeros, not a uniform softmax over garbage logits."""
    q, kc, vc = _mk_decode()
    out = np.asarray(decode_attention(q, kc, vc, length=0))
    assert np.all(out == 0.0)
    # window=0 excludes even the newest slot, for every batch row
    out = np.asarray(decode_attention(q, kc, vc, length=8, window=0))
    assert np.all(out == 0.0)
    # per-batch: row 0 empty -> zeros; row 1 live -> matches the ref
    out = np.asarray(decode_attention(q, kc, vc, length=jnp.array([0, 8])))
    assert np.all(out[0] == 0.0)
    ref = attention_ref(q[1:], kc[1:, :8], vc[1:, :8], causal=True, q_offset=7)
    np.testing.assert_allclose(out[1:], np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert np.any(out[1] != 0.0)


def test_decode_per_batch_lengths_match_ref():
    lengths = (40, 17)
    q, kc, vc = _mk_decode()
    out = np.asarray(decode_attention(q, kc, vc, length=jnp.array(lengths)))
    for i, L in enumerate(lengths):
        ref = attention_ref(
            q[i : i + 1], kc[i : i + 1, :L], vc[i : i + 1, :L],
            causal=True, q_offset=L - 1,
        )
        np.testing.assert_allclose(out[i : i + 1], np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_decode_window_includes_newest_slot():
    """A sliding window always covers slot length-1 (the query's own
    position); window=1 attends to exactly that slot."""
    L = 40
    q, kc, vc = _mk_decode()
    out = np.asarray(decode_attention(q, kc, vc, length=L, window=1))
    # attention over a single slot: softmax == 1 -> output is v[L-1]
    b, _, h, d = q.shape
    kvh = kc.shape[2]
    # heads are kvh-major in the GQA grouping: head i reads kv head i // g
    want = np.repeat(np.asarray(vc)[:, L - 1], h // kvh, axis=1)
    want = want.reshape(b, 1, h, d)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
    # boundary inclusion/exclusion: window=w sees slots [L-w, L-1]
    w = 16
    outw = decode_attention(q, kc, vc, length=L, window=w)
    ref = attention_ref(q, kc[:, :L], vc[:, :L], causal=True, window=w,
                        q_offset=L - 1)
    np.testing.assert_allclose(np.asarray(outw), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    # and perturbing the newest in-window slot changes the output
    kc2 = kc.at[:, L - 1].add(1.0)
    out2 = decode_attention(q, kc2, vc, length=L, window=w)
    assert not np.allclose(np.asarray(outw), np.asarray(out2))
