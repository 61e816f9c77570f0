"""DeepSeek-V3: the lowered stream against the plain model, the absorbed
decode against the naive forward, the benchmark's reference lowering
against the program's, and the deepseek-moe-16b stream left as it was.

- (a) ``lower_network(reduced(deepseek-v3))`` in train, prefill and
  decode equals the weight matmuls of the ``models/`` forward, traced
  by ``jax.make_jaxpr``: every dot under a scope named as a stream
  GEMM, by (M, K, N) and count, and total MACs. Routed experts run as
  one ``ragged_dot`` over all routed rows, so they compare by (K, N)
  and MACs: with t * top_k divisible by the experts, the lowering's
  ceil(t * top_k / E) rows per expert add up to the rows routed,
  whatever the seeded routing.
- (b) prefill then absorbed decode through the latent cache gives the
  naive full forward's logits within ``DECODE_TOL``; in bfloat16 it
  does not.
- (c) ``bench/lowering/DeepSeek-V3.py`` equals ``lower_network`` at
  published widths in every mode.
- (d) ``deepseek-moe-16b`` lowers to the same 7 rows and counts.
"""

import dataclasses
import importlib.util
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jcore

from repro.analysis.traffic import kv_bytes_per_context_token
from repro.config import ShapeConfig
from repro.configs import SHAPES, get_config, reduced
from repro.core.network import lower_network
from repro.models import build
from repro.models.decoder import decoder_forward

ROOT = pathlib.Path(__file__).resolve().parent.parent
V3 = get_config("deepseek-v3")
SMALL = reduced(V3)
EXPERTS = ("moe.expert.in", "moe.expert.out")
#: every name the lowering gives a GEMM of this model (the stream keeps
#: one name per shape, so a merged stream shows fewer)
GEMM_NAMES = {"attn.q_a", "attn.q_b", "attn.kv_a", "attn.kv_b", "attn.uk",
              "attn.uv", "attn.o", "mlp.in", "mlp.out", "moe.router",
              "moe.shared.in", "moe.shared.out", "logits", *EXPERTS}

#: Relative max error of absorbed decode against the naive forward's
#: logits. Both compute the same float32 products in different orders
#: (scores against the latent instead of the expanded keys), so they
#: differ by accumulated float32 rounding: 1.1e-6 to 1.6e-6 over these
#: 4 layers and seeds (unit roundoff 6e-8 times contractions of up to
#: 256 terms). 1e-4 leaves ~60x room above that, and stays far below
#: bfloat16's rounding (unit roundoff 3.9e-3), which reads 2e-2 to 4e-1
#: here (it also flips routing choices).
DECODE_TOL = 1e-4


# ---------------------------------------------------------------------------
# (a) the stream against the traced forward
# ---------------------------------------------------------------------------

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


def _trace(mode, batch, seq):
    model = build(SMALL)
    params = model.abstract_params()
    if mode == "decode":
        cache = model.abstract_cache(batch, seq, jnp.float32)
        token = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
        return jax.make_jaxpr(model.decode)(params, cache, {"token": token})
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    return jax.make_jaxpr(
        lambda p, t: decoder_forward(p, t, SMALL, mode=mode)[0])(params, tokens)


@pytest.mark.parametrize("mode,batch,seq", [
    ("train", 1, 16), ("prefill", 1, 16), ("decode", 8, 32)])
def test_stream_equals_the_traced_forward(mode, batch, seq):
    stream = lower_network(SMALL, ShapeConfig(mode, seq, batch, mode))
    t = batch if mode == "decode" else seq
    assert (t * SMALL.top_k) % SMALL.n_experts == 0
    traced = _traced_gemms(_trace(mode, batch, seq).jaxpr, GEMM_NAMES)

    want = _by_shape((g.name, g.M, g.K, g.N, g.count) for g in stream.gemms)
    got = _by_shape(traced)
    assert got[0] == want[0]  # every non-expert (M, K, N) and its count
    for shape, (name,) in want[1].items():
        assert name in got[1][shape], (shape, name, got[1][shape])
    assert got[2] == want[2]  # experts: (K, N) and MACs
    assert sum(M * K * N * n for _, M, K, N, n in traced) == stream.total_macs
    # the path follows the mode: absorbed GEMMs in decode only
    names = {row[0] for row in traced}
    assert names == GEMM_NAMES - ({"attn.kv_b"} if mode == "decode"
                                  else {"attn.uk", "attn.uv"})


def test_reduced_keeps_the_pattern():
    assert 0 < SMALL.n_dense_layers < SMALL.n_layers
    assert SMALL.kv_lora_rank < SMALL.n_heads * (SMALL.qk_nope_head_dim + SMALL.v_head_dim)
    assert SMALL.n_experts % SMALL.n_expert_groups == 0
    assert SMALL.topk_groups * SMALL.n_experts // SMALL.n_expert_groups >= SMALL.top_k


# ---------------------------------------------------------------------------
# (b) absorbed decode through the latent cache vs the naive forward
# ---------------------------------------------------------------------------

def _decode_error(cfg, seed):
    """Relative max error of prefill(S) + decode(token S) against the
    float32 naive forward over S + 1 tokens, at position S."""
    f32 = build(SMALL)
    params = f32.init(jax.random.PRNGKey(seed))
    B, S = 2, 24
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (B, S + 1), 0, SMALL.vocab)
    with jax.default_matmul_precision("highest"):
        full, _ = f32.prefill(params, {"tokens": toks})
        model = build(cfg)
        cast = jax.tree.map(lambda a: a.astype(cfg.param_dtype), params)
        _, cache = model.prefill(cast, {"tokens": toks[:, :S]}, max_len=S + 4)
        got, _ = model.decode(cast, cache, {"token": toks[:, S:]})
    want = np.asarray(full[:, S], np.float64)
    got = np.asarray(got[:, 0], np.float64)
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("seed", [0, 7, 123])
def test_absorbed_decode_matches_the_naive_forward(seed):
    assert _decode_error(SMALL, seed) < DECODE_TOL


@pytest.mark.parametrize("seed", [0, 7, 123])
def test_bfloat16_decode_fails_the_tolerance(seed):
    bf16 = dataclasses.replace(SMALL, param_dtype="bfloat16", compute_dtype="bfloat16")
    assert _decode_error(bf16, seed) > DECODE_TOL


def test_latent_cache_bytes():
    """One latent plus the rope key per layer and token, not K and V."""
    per_token = (V3.kv_lora_rank + V3.qk_rope_head_dim) * V3.n_layers * 2
    assert kv_bytes_per_context_token(V3) == per_token == 576 * 61 * 2


def test_parameter_counts():
    assert 660e9 <= V3.n_params <= 685e9
    assert 35e9 <= V3.n_active_params <= 39e9
    assert build(V3).n_params == pytest.approx(V3.n_params, rel=1e-3)


# ---------------------------------------------------------------------------
# (c) the benchmark's reference lowering at published widths
# ---------------------------------------------------------------------------

def _bench_config():
    return json.loads((ROOT / "bench" / "configs" / "DeepSeek-V3.json").read_text())


def _bench_lower():
    path = ROOT / "bench" / "lowering" / "DeepSeek-V3.py"
    spec = importlib.util.spec_from_file_location("bench_lowering_v3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.lower


@pytest.mark.parametrize("shape", ["train_4k", "prefill_32k", "decode_32k"])
def test_bench_lowering_equals_the_program(shape):
    sh = SHAPES[shape]
    gemms, counts = _bench_lower()(_bench_config()["model"], dataclasses.asdict(sh))
    stream = lower_network(V3, sh)
    assert [tuple(g) for g in gemms] == [tuple(r) for r in stream.workloads.tolist()]
    assert list(counts) == stream.counts.tolist()


def test_bench_config_is_the_published_one():
    conf = _bench_config()
    m = conf["model"]
    pairs = {"n_layers": "num_hidden_layers", "n_dense_layers": "first_k_dense_replace",
             "d_model": "hidden_size", "n_heads": "num_attention_heads",
             "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
             "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
             "v_head_dim": "v_head_dim", "d_ff": "intermediate_size",
             "vocab": "vocab_size", "n_experts": "n_routed_experts",
             "n_shared_experts": "n_shared_experts", "top_k": "num_experts_per_tok",
             "expert_d_ff": "moe_intermediate_size", "act": "hidden_act"}
    assert {k: m[k] for k in pairs} == {k: conf[v] for k, v in pairs.items()}
    assert {k: getattr(V3, k) for k in pairs} == {k: m[k] for k in pairs}
    assert conf["reduced"] == [] and conf["study"]["workload"]["arch"] == V3.name


def test_decode_stream_is_as_sized():
    """14 unique GEMMs, 8 of them new to the benchmark's streams."""
    stream = lower_network(V3, SHAPES["decode_32k"])
    by_name = {g.name: g for g in stream.gemms}
    assert len(stream.gemms) == 14
    assert by_name["moe.expert.in"].M == 4  # ceil(128 * 8 / 256)
    assert (by_name["attn.uk"].K, by_name["attn.uk"].N) == (128, 512)
    assert by_name["attn.uv"].count == 128 * 61
    assert by_name["mlp.out"].count == 3 and by_name["moe.router"].count == 58


# ---------------------------------------------------------------------------
# (d) deepseek-moe-16b unchanged
# ---------------------------------------------------------------------------

MOE_16B = {  # (name, M, K, N, count), as lowered before latent attention
    "prefill_32k": [("attn.q", 32768, 2048, 2048, 3584),
                    ("moe.router", 32768, 2048, 64, 896),
                    ("moe.expert.in", 3072, 2048, 1408, 114688),
                    ("moe.expert.out", 3072, 1408, 2048, 57344),
                    ("moe.shared.in", 32768, 2048, 1408, 3584),
                    ("moe.shared.out", 32768, 1408, 2048, 1792),
                    ("logits", 32768, 2048, 102400, 32)],
    "decode_32k": [("attn.q", 128, 2048, 2048, 112),
                   ("moe.router", 128, 2048, 64, 28),
                   ("moe.expert.in", 12, 2048, 1408, 3584),
                   ("moe.expert.out", 12, 1408, 2048, 1792),
                   ("moe.shared.in", 128, 2048, 1408, 112),
                   ("moe.shared.out", 128, 1408, 2048, 56),
                   ("logits", 128, 2048, 102400, 1)],
}


@pytest.mark.parametrize("shape", sorted(MOE_16B))
def test_deepseek_moe_16b_stream_is_unchanged(shape):
    stream = lower_network(get_config("deepseek-moe-16b"), SHAPES[shape])
    assert [(g.name, g.M, g.K, g.N, g.count) for g in stream.gemms] == MOE_16B[shape]
