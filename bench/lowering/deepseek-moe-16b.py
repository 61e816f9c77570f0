"""Reference lowering of ``deepseek-moe-16b``: the GEMM stream that
``bench/reference.py`` searches and prices for a ``schedule`` study of
this configuration. Found by the configuration's name; imports nothing
of the program.
"""

from __future__ import annotations


def _cdiv(a, b):
    return -(-a // b)


def lower(model: dict, shape: dict):
    """GEMM stream of one execution of a MoE decoder: ``[(M, K, N), ...]``
    unique shapes in first-seen order and their multiplicities.

    Weight GEMMs only: q/k/v/o projections, router, routed experts at
    the expected per-expert token count ceil(t * top_k / n_experts),
    shared experts, logits. Prefill streams one sequence per pass
    (M = seq_len, counts times the batch); decode is one batched step
    (M = batch). Gated (silu) FFNs run two input projections.
    """
    if shape["mode"] == "decode":
        t, mult = shape["global_batch"], 1
    else:
        t, mult = shape["seq_len"], shape["global_batch"]
    L, d = model["n_layers"], model["d_model"]
    q_out = model["n_heads"] * model["head_dim"]
    kv_out = model["n_kv_heads"] * model["head_dim"]
    E, ff = model["n_experts"], model["expert_d_ff"]
    n_in = 2 if model["act"] == "silu" else 1
    routed = max(1, _cdiv(t * model["top_k"], E))
    items = [
        (t, d, q_out, L), (t, d, kv_out, 2 * L), (t, q_out, d, L),
        (t, d, E, L),
        (routed, d, ff, n_in * E * L), (routed, ff, d, E * L),
        (t, d, ff, n_in * model["n_shared_experts"] * L),
        (t, ff, d, model["n_shared_experts"] * L),
        (t, d, model["vocab"], 1),
    ]
    merged: dict[tuple[int, int, int], int] = {}
    for M, K, N, n in items:
        if n > 0:
            merged[(M, K, N)] = merged.get((M, K, N), 0) + n * mult
    return list(merged), list(merged.values())
