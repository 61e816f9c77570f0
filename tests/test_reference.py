"""``models/`` as the plain reference the lowering is held against.

- The lowered stream of ``reduced(arch)`` in train, prefill and decode
  equals the weight matmuls of the ``models/`` forward, traced by
  ``jax.make_jaxpr``: every dot under a scope named as a stream GEMM,
  by (M, K, N) and count, and total MACs. Routed experts run as one
  ``ragged_dot`` over all routed rows, so they compare by (K, N) and
  MACs: with t * top_k divisible by the experts, the lowering's
  ceil(t * top_k / E) rows per expert add up to the rows routed,
  whatever the seeded routing.
- The model's kv cache per context token equals what
  ``analysis.traffic`` charges a decode step for it.

``deepseek-v3``'s own cases live in ``test_deepseek_v3.py``, which
shares the walker below. Families whose weight GEMMs carry no scopes
yet (whisper, xLSTM, zamba2) are not traced.
"""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.extend import core as jcore

from repro.analysis.traffic import kv_bytes_per_context_token
from repro.config import ShapeConfig
from repro.configs import REGISTRY, reduced
from repro.core.network import lower_network
from repro.models import build
from repro.models.decoder import decoder_forward

EXPERTS = ("moe.expert.in", "moe.expert.out")
#: every scope name ``models/`` gives a weight GEMM of the traced
#: families (the stream keeps one name per shape, so a merged stream
#: shows fewer)
GEMM_NAMES = {"attn.q", "attn.kv", "attn.o", "cross.q", "cross.kv", "cross.o",
              "mlp.in", "mlp.out", "moe.router", "moe.shared.in",
              "moe.shared.out", "logits", *EXPERTS}
TRACED = ["smollm-135m", "qwen2.5-3b", "qwen2-72b", "gemma3-1b",
          "deepseek-moe-16b", "llama4-scout-17b-a16e", "llama-3.2-vision-11b"]
#: (mode, batch, seq): one sequence in train and prefill, a batch of
#: single tokens against a cache in decode
MODES = [("train", 1, 16), ("prefill", 1, 16), ("decode", 8, 32)]


def _sub_jaxprs(params):
    for v in params.values():
        for x in v if isinstance(v, (tuple, list)) else (v,):
            if isinstance(x, jcore.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jcore.Jaxpr):
                yield x


def _traced_gemms(jaxpr, names, mult=1, scope=""):
    """(name, M, K, N, count) of every dot under a scope named in
    ``names``, scan trips multiplied in. The activation is the left
    operand (the model's weight GEMMs are all ``x @ w``)."""
    out = []
    for eqn in jaxpr.eqns:
        stack = "/".join(x for x in (scope, str(eqn.source_info.name_stack)) if x)
        prim = eqn.primitive.name
        if prim in ("dot_general", "ragged_dot_general"):
            found = [part for part in stack.split("/") if part in names]
            if not found:
                continue  # activation x activation (scores, values)
            lhs, rhs = (v.aval.shape for v in eqn.invars[:2])
            if prim == "ragged_dot_general":  # (m, k) x (groups, k, n)
                M, K, N, batch = lhs[0], lhs[1], rhs[2], 1
            else:
                (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
                M = math.prod(d for i, d in enumerate(lhs) if i not in (*lc, *lb))
                K = math.prod(lhs[i] for i in lc)
                N = math.prod(d for i, d in enumerate(rhs) if i not in (*rc, *rb))
                batch = math.prod(lhs[i] for i in lb)
            out.append((found[-1], M, K, N, mult * batch))
            continue
        trips = eqn.params["length"] if prim == "scan" else 1
        for sub in _sub_jaxprs(eqn.params):
            out += _traced_gemms(sub, names, mult * trips, stack)
    return out


def _by_shape(rows):
    """{(M, K, N): count} and {(M, K, N): names} of non-expert rows;
    {name: (K, N, MACs)} of the routed experts."""
    counts, names, experts = {}, {}, {}
    for name, M, K, N, n in rows:
        if name in EXPERTS:
            k, n_, macs = experts.get(name, (K, N, 0))
            assert (k, n_) == (K, N), name
            experts[name] = (K, N, macs + M * K * N * n)
        else:
            counts[(M, K, N)] = counts.get((M, K, N), 0) + n
            names.setdefault((M, K, N), set()).add(name)
    return counts, names, experts


def _trace(cfg, mode, batch, seq):
    """The jaxpr of one forward of the decoder ``cfg`` on abstract
    parameters: a decode step against a ``seq``-slot cache, or a
    train/prefill pass over ``seq`` tokens."""
    model = build(cfg)
    params = model.abstract_params()
    if mode == "decode":
        cache = model.abstract_cache(batch, seq, jnp.float32)
        token = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        return jax.make_jaxpr(model.decode)(params, cache, {"token": token})
    inputs = {"tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32)}
    if cfg.family == "vlm":
        inputs["image_embeds"] = jax.ShapeDtypeStruct(
            (batch, cfg.n_image_tokens, cfg.d_model), jnp.float32)
    return jax.make_jaxpr(lambda p, b: decoder_forward(
        p, b["tokens"], cfg, mode=mode, image_embeds=b.get("image_embeds"))[0]
    )(params, inputs)


def assert_stream_equals_the_traced_forward(cfg, mode, batch, seq, names):
    """The lowered stream of ``cfg`` against its traced forward; returns
    the traced rows."""
    stream = lower_network(cfg, ShapeConfig(mode, seq, batch, mode))
    traced = _traced_gemms(_trace(cfg, mode, batch, seq).jaxpr, names)

    want = _by_shape((g.name, g.M, g.K, g.N, g.count) for g in stream.gemms)
    got = _by_shape(traced)
    assert got[0] == want[0]  # every non-expert (M, K, N) and its count
    for shape, (name,) in want[1].items():
        assert name in got[1][shape], (shape, name, got[1][shape])
    assert got[2] == want[2]  # experts: (K, N) and MACs
    assert sum(M * K * N * n for _, M, K, N, n in traced) == stream.total_macs
    return traced


@pytest.mark.parametrize("mode,batch,seq", MODES, ids=[m[0] for m in MODES])
@pytest.mark.parametrize("arch", TRACED)
def test_stream_equals_the_traced_forward(arch, mode, batch, seq):
    cfg = reduced(REGISTRY[arch])
    t = batch if mode == "decode" else seq
    assert cfg.family != "moe" or (t * cfg.top_k) % cfg.n_experts == 0
    assert_stream_equals_the_traced_forward(cfg, mode, batch, seq, GEMM_NAMES)


def _cache_bytes(model, max_len):
    cache = model.abstract_cache(1, max_len)
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))


@pytest.mark.parametrize("arch", [
    pytest.param(a, marks=pytest.mark.xfail(
        strict=True, reason="the model caches k/v per context token in its "
        "self-attention layers only (256 B a token, reduced); traffic "
        "charges every layer, cross-attention ones too (512 B)"))
    if a == "llama-3.2-vision-11b" else a
    for a in sorted(REGISTRY)])
def test_cache_bytes_match_traffic(arch):
    """bfloat16 cache bytes one more context token adds, as the serve
    cost model charges them; fixed-size state (SSM, cross k/v) cancels."""
    cfg = reduced(REGISTRY[arch])
    model = build(cfg)
    per_token = (_cache_bytes(model, 64) - _cache_bytes(model, 32)) / 32
    assert per_token == kv_bytes_per_context_token(cfg)
