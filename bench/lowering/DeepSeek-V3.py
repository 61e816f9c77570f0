"""Reference lowering of ``DeepSeek-V3``: the GEMM stream that
``bench/reference.py`` searches and prices for a ``schedule`` study of
this configuration. Found by the configuration's name; imports nothing
of the program.
"""

from __future__ import annotations


def _cdiv(a, b):
    return -(-a // b)


def lower(model: dict, shape: dict):
    """GEMM stream of one execution of DeepSeek-V3: ``[(M, K, N), ...]``
    unique shapes in first-seen order and their multiplicities.

    Weight GEMMs only, from the layer equations (arXiv:2412.19437 §2.1):

    - latent attention on every layer: q = W_qb norm(W_qa h) per head
      (nope + rope widths); [c, k_pe] = W_kva h, the latent c and one
      rope key the cache keeps; u = W_o o. Prefill and training expand
      every token's latent, [k_nope, v] = W_kvb c (the naive path).
      Decode absorbs W_kvb (DeepSeek-V3 ``inference/model.py``,
      ``attn_impl="absorb"``): per head, q_nope W_UK (nope -> latent)
      and o_latent W_UV (latent -> v) at M = batch;
    - the first ``n_dense_layers`` FFNs dense (width ``d_ff``), the
      rest MoE: router, routed experts at the expected per-expert token
      count ceil(t * top_k / n_experts), shared experts;
    - logits.

    Prefill and training stream one sequence per pass (M = seq_len,
    counts times the batch); decode is one batched step (M = batch).
    Gated (silu) FFNs run two input projections.
    """
    if shape["mode"] == "decode":
        t, mult = shape["global_batch"], 1
    else:
        t, mult = shape["seq_len"], shape["global_batch"]
    L, d, H = model["n_layers"], model["d_model"], model["n_heads"]
    L_dense = model["n_dense_layers"]
    L_moe = L - L_dense
    q_lat, kv_lat = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, dv = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                      model["v_head_dim"])
    E, ff, shared = model["n_experts"], model["expert_d_ff"], model["n_shared_experts"]
    n_in = 2 if model["act"] == "silu" else 1
    routed = max(1, _cdiv(t * model["top_k"], E))

    items = [
        (t, d, q_lat, L),                      # q down
        (t, q_lat, H * (nope + rope), L),      # q up, every head
        (t, d, kv_lat + rope, L),              # kv latent + rope key
    ]
    if shape["mode"] == "decode":
        items += [
            (t, nope, kv_lat, H * L),          # W_UK, per head
            (t, kv_lat, dv, H * L),            # W_UV, per head
        ]
    else:
        items.append((t, kv_lat, H * (nope + dv), L))  # kv up, every token
    items += [
        (t, H * dv, d, L),                     # o
        (t, d, model["d_ff"], n_in * L_dense),
        (t, model["d_ff"], d, L_dense),
        (t, d, E, L_moe),                      # router
        (routed, d, ff, n_in * E * L_moe),
        (routed, ff, d, E * L_moe),
        (t, d, ff, n_in * shared * L_moe),
        (t, ff, d, shared * L_moe),
        (t, d, model["vocab"], 1),
    ]
    merged: dict[tuple[int, int, int], int] = {}
    for M, K, N, n in items:
        if n > 0:
            merged[(M, K, N)] = merged.get((M, K, N), 0) + n * mult
    return list(merged), list(merged.values())
