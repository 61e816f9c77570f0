"""Unified decoder LM covering the assigned architecture families.

One parameterized decoder serves: dense (llama/smollm/qwen), local:global
patterns (gemma3), MoE FFNs (deepseek-moe, llama4-scout), latent
attention over leading dense layers then MoE (deepseek-v3), vision
cross-attention interleave (llama-3.2-vision), Mamba2+shared-attention
hybrid (zamba2) and xLSTM stacks (mLSTM/sLSTM).

Layer stacking: homogeneous runs of layers are stacked and executed
with ``lax.scan`` (compile time O(1) in depth — required for
qwen2-72b's 80 layers); per-layer attention metadata (sliding window,
rope theta) rides along as scanned arrays so heterogeneous attention
patterns (gemma3's 5:1) still scan. Heterogeneous *structures* (vision
cross-attn every 5th, zamba2's shared block every 6th) use grouped
scans.

Modes: ``train`` (full seq, loss-ready logits), ``prefill`` (returns KV
caches / SSM states), ``decode`` (one token; caches advance).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..config import ArchConfig
from .layers import (
    attention, attn_defs, compute_cross_kv, embed_defs, embed_tokens,
    mlp, mlp_defs, rmsnorm, rmsnorm_def, unembed,
)
from .mla import mla_attention, mla_defs, mla_init_cache
from .moe import moe_block, moe_defs
from .params import ParamDef, stack_defs
from .ssm import mamba_block, mamba_defs, mamba_init_state
from .xlstm import (
    mlstm_block, mlstm_defs, mlstm_init_state,
    slstm_block, slstm_defs, slstm_init_state,
)

__all__ = ["decoder_defs", "decoder_forward", "init_cache", "layer_metadata"]

_GLOBAL_WINDOW = 2**30  # "window" larger than any sequence = global attn


# --------------------------------------------------------------------------
# Parameter trees
# --------------------------------------------------------------------------


def _block_defs(cfg: ArchConfig, moe: bool | None = None):
    moe = cfg.family == "moe" if moe is None else moe
    d = {
        "ln1": rmsnorm_def(cfg.d_model),
        "attn": mla_defs(cfg) if cfg.kv_lora_rank else attn_defs(cfg),
        "ln2": rmsnorm_def(cfg.d_model),
        "ffn": moe_defs(cfg) if moe else mlp_defs(cfg),
    }
    return d


def decoder_defs(cfg: ArchConfig):
    defs = {
        "embed": embed_defs(cfg),
        "final_norm": rmsnorm_def(cfg.d_model),
    }
    fam = cfg.family
    if fam in ("dense", "moe"):
        # leading dense layers (deepseek-v3: 3) stack apart from the rest
        n_dense = cfg.n_dense_layers
        if n_dense:
            defs["dense_layers"] = stack_defs(_block_defs(cfg, moe=False), n_dense)
        defs["layers"] = stack_defs(_block_defs(cfg), cfg.n_layers - n_dense)
    elif fam == "vlm":
        period = cfg.cross_every  # every Nth layer is a cross layer
        n_groups = cfg.n_layers // period
        n_self = period - 1
        self_defs = stack_defs(stack_defs(_block_defs(cfg), n_self), n_groups)
        cross = {
            "ln1": rmsnorm_def(cfg.d_model),
            "attn": attn_defs(cfg),
            "gate": ParamDef((1,), ("one",), init="zeros"),
            "ln2": rmsnorm_def(cfg.d_model),
            "ffn": mlp_defs(cfg),
        }
        defs["layers"] = self_defs
        defs["cross_layers"] = stack_defs(cross, n_groups)
    elif fam == "hybrid":
        period = cfg.attn_every
        n_groups = cfg.n_layers // period
        defs["layers"] = stack_defs(stack_defs(mamba_defs(cfg), period), n_groups)
        defs["shared_attn"] = {  # ONE set of weights, applied every period
            "ln1": rmsnorm_def(cfg.d_model),
            "attn": attn_defs(cfg),
            "ln2": rmsnorm_def(cfg.d_model),
            "ffn": mlp_defs(cfg),
        }
    elif fam == "ssm":  # xLSTM
        blocks = []
        for i in range(cfg.n_layers):
            kind = "slstm" if i in cfg.slstm_at else "mlstm"
            sub = slstm_defs(cfg) if kind == "slstm" else mlstm_defs(cfg)
            blocks.append({"kind_" + kind: sub, "ln": rmsnorm_def(cfg.d_model)})
        defs["blocks"] = blocks
    else:
        raise ValueError(f"decoder does not handle family {fam}")
    return defs


def layer_metadata(cfg: ArchConfig, n: int | None = None):
    """Per-layer (window, theta) arrays for scanned attention layers."""
    n = n or cfg.n_layers
    wins, thetas = [], []
    for i in range(n):
        is_global = cfg.global_every and ((i + 1) % cfg.global_every == 0)
        if cfg.sliding_window and not is_global:
            wins.append(cfg.sliding_window)
        else:
            wins.append(_GLOBAL_WINDOW)
        if is_global and cfg.global_rope_theta:
            thetas.append(cfg.global_rope_theta)
        else:
            thetas.append(cfg.rope_theta)
    return jnp.asarray(wins, jnp.int32), jnp.asarray(thetas, jnp.float32)


# --------------------------------------------------------------------------
# KV / state cache construction
# --------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Decode-ready cache pytree for the whole model."""
    kvh, hd = cfg.n_kv_heads, cfg.head_dim_

    def kv(b=batch, s=max_len):
        if cfg.kv_lora_rank:
            return mla_init_cache(cfg, b, s, dtype)
        return {
            "k": jnp.zeros((b, s, kvh, hd), dtype),
            "v": jnp.zeros((b, s, kvh, hd), dtype),
            "length": jnp.int32(0),
        }

    fam = cfg.family
    if fam in ("dense", "moe"):
        def stacked(n):
            return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape), kv())

        n_dense = cfg.n_dense_layers
        out = {"layers": stacked(cfg.n_layers - n_dense)}
        if n_dense:
            out["dense_layers"] = stacked(n_dense)
        return out
    if fam == "vlm":
        period = cfg.cross_every
        n_groups = cfg.n_layers // period
        n_self = period - 1
        self_kv = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups, n_self) + x.shape), kv()
        )
        cross = {
            "k": jnp.zeros((n_groups, batch, cfg.n_image_tokens, kvh, hd), dtype),
            "v": jnp.zeros((n_groups, batch, cfg.n_image_tokens, kvh, hd), dtype),
        }
        return {"layers": self_kv, "cross": cross}
    if fam == "hybrid":
        period = cfg.attn_every
        n_groups = cfg.n_layers // period
        m = mamba_init_state(cfg, batch, dtype)
        mamba_stack = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups, period) + x.shape), m
        )
        attn_stack = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_groups,) + x.shape), kv()
        )
        return {"mamba": mamba_stack, "attn": attn_stack}
    if fam == "ssm":
        states = []
        for i in range(cfg.n_layers):
            if i in cfg.slstm_at:
                states.append(slstm_init_state(cfg, batch))
            else:
                states.append(mlstm_init_state(cfg, batch))
        return {"blocks": states}
    raise ValueError(fam)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------


def _attn_mlp_block(lp, x, cfg, *, mode, cache, window, theta, cross_kv=None):
    if "wkv_a" in lp["attn"]:  # latent attention (no window, no cross)
        h, new_cache = mla_attention(
            lp["attn"], rmsnorm(x, lp["ln1"], cfg.norm_eps), cfg,
            mode=mode, cache=cache, theta=theta,
        )
    else:
        h, new_cache = attention(
            lp["attn"],
            rmsnorm(x, lp["ln1"], cfg.norm_eps),
            cfg,
            mode=mode,
            cache=cache,
            window=window,
            theta=theta,
            cross_kv=cross_kv,
        )
    if "gate" in lp:  # gated cross-attn (llama-3.2-vision)
        h = jnp.tanh(lp["gate"].astype(jnp.float32)).astype(h.dtype) * h
    x = x + h
    y = rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if "router" in lp["ffn"]:
        y = moe_block(lp["ffn"], y, cfg)
    else:
        y = mlp(lp["ffn"], y, cfg.act)
    return x + y, new_cache


def _scan_blocks(stacked_params, x, cfg, *, mode, caches, metas, remat=False):
    """lax.scan over a homogeneous stack of attn+ffn blocks.
    ``cfg.scan_layers=False`` fully unrolls (used by the dry-run's cost
    variants so cost_analysis counts every layer)."""
    win_arr, theta_arr = metas

    def body(carry, xs):
        lp, w, th, cache_l = xs
        y, new_cache = _attn_mlp_block(
            lp, carry, cfg, mode=mode, cache=cache_l, window=w, theta=th
        )
        return y, new_cache

    if remat:
        policy = (
            jax.checkpoint_policies.save_only_these_names("gathered_w")
            if remat == "save_gathered" else None
        )
        body = jax.checkpoint(body, policy=policy)
    x, new_caches = jax.lax.scan(
        body, x, (stacked_params, win_arr, theta_arr, caches),
        unroll=not cfg.scan_layers,
    )
    return x, new_caches


def decoder_forward(
    params,
    tokens,  # (B, S) int32
    cfg: ArchConfig,
    *,
    mode: str,
    cache=None,
    image_embeds=None,  # (B, n_img, E) for vlm
    max_len: int = 0,  # decode capacity for prefill-produced caches
    remat: bool = False,
):
    """Returns (logits, new_cache)."""
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    x = embed_tokens(params["embed"], tokens, compute_dtype)
    b, s, e = x.shape
    fam = cfg.family
    new_cache = None

    if fam in ("dense", "moe"):
        wins, thetas = layer_metadata(cfg)
        nd = cfg.n_dense_layers
        new_cache = {}
        # leading dense stack (if any), then the rest
        for key, sl in (("dense_layers", slice(None, nd)), ("layers", slice(nd, None))):
            if key not in params:
                continue
            x, ncache = _scan_blocks(
                params[key], x, cfg, mode=mode,
                caches=cache[key] if cache is not None else None,
                metas=(wins[sl], thetas[sl]),
                remat=(remat if mode == "train" else False),
            )
            new_cache[key] = ncache
        if mode == "train":
            new_cache = None

    elif fam == "vlm":
        period = cfg.cross_every
        n_groups = cfg.n_layers // period
        n_self = period - 1
        win_all, theta_all = layer_metadata(cfg, n_groups * n_self)
        win_g = win_all.reshape(n_groups, n_self)
        theta_g = theta_all.reshape(n_groups, n_self)
        self_caches = cache["layers"] if cache is not None else None
        cross_cache = cache["cross"] if cache is not None else None
        new_self, new_cross = [], []
        for g in range(n_groups):
            sp = jax.tree.map(lambda a: a[g], params["layers"])
            cp = jax.tree.map(lambda a: a[g], params["cross_layers"])
            cg = (
                jax.tree.map(lambda a: a[g], self_caches)
                if self_caches is not None
                else None
            )
            x, nc = _scan_blocks(
                sp, x, cfg, mode=mode, caches=cg, metas=(win_g[g], theta_g[g]),
                remat=(remat if mode == "train" else False),
            )
            if mode == "decode":
                ckv = (cross_cache["k"][g], cross_cache["v"][g])
            else:
                ckv = compute_cross_kv(cp["attn"], image_embeds, cfg)
            x, _ = _attn_mlp_block(
                cp, x, cfg, mode=mode, cache=None, window=None, theta=None,
                cross_kv=ckv,
            )
            if mode != "train":
                new_self.append(nc)
                new_cross.append(ckv)
        if mode != "train":
            new_cache = {
                "layers": jax.tree.map(lambda *xs: jnp.stack(xs), *new_self),
                "cross": {
                    "k": jnp.stack([kv[0] for kv in new_cross]),
                    "v": jnp.stack([kv[1] for kv in new_cross]),
                },
            }

    elif fam == "hybrid":
        period = cfg.attn_every
        n_groups = cfg.n_layers // period
        mamba_caches = cache["mamba"] if cache is not None else None
        attn_caches = cache["attn"] if cache is not None else None
        shared = params["shared_attn"]
        new_mamba, new_attn = [], []
        for g in range(n_groups):
            gp = jax.tree.map(lambda a: a[g], params["layers"])
            gc = (
                jax.tree.map(lambda a: a[g], mamba_caches)
                if mamba_caches is not None
                else None
            )

            def mbody(carry, xs):
                lp, st = xs
                y, new_st = mamba_block(lp, carry, cfg, mode=mode, state=st)
                return carry + y, new_st

            if gc is None:
                gc_in = jax.tree.map(
                    lambda x_: jnp.broadcast_to(x_, (period,) + x_.shape),
                    mamba_init_state(cfg, b, compute_dtype),
                )
            else:
                gc_in = gc
            mb = jax.checkpoint(mbody) if (remat and mode == "train") else mbody
            x, nst = jax.lax.scan(mb, x, (gp, gc_in), unroll=not cfg.scan_layers)
            ac = (
                jax.tree.map(lambda a: a[g], attn_caches)
                if attn_caches is not None
                else None
            )
            x, nac = _attn_mlp_block(
                shared, x, cfg, mode=mode, cache=ac,
                window=None, theta=cfg.rope_theta,
            )
            if mode != "train":
                new_mamba.append(nst)
                new_attn.append(nac)
        if mode != "train":
            new_cache = {
                "mamba": jax.tree.map(lambda *xs: jnp.stack(xs), *new_mamba),
                "attn": jax.tree.map(lambda *xs: jnp.stack(xs), *new_attn),
            }

    elif fam == "ssm":
        states = cache["blocks"] if cache is not None else [None] * cfg.n_layers
        new_states = []
        for i, bp in enumerate(params["blocks"]):
            block = slstm_block if i in cfg.slstm_at else mlstm_block
            sub = bp["kind_slstm"] if i in cfg.slstm_at else bp["kind_mlstm"]
            y, nst = block(sub, rmsnorm(x, bp["ln"], cfg.norm_eps), cfg,
                           mode=mode, state=states[i])
            x = x + y
            new_states.append(nst)
        if mode != "train":
            new_cache = {"blocks": new_states}

    else:
        raise ValueError(fam)

    x = rmsnorm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings)

    if mode == "prefill" and max_len and new_cache is not None:
        new_cache = _pad_cache_tree(new_cache, max_len)
    return logits, new_cache


def _pad_cache_tree(cache, max_len):
    """Pad every kv buffer (dim -3 = seq; a latent cache's dim -2) up
    to max_len."""

    def rec(node):
        if isinstance(node, dict) and "c" in node and "length" in node:
            s = node["c"].shape[-2]
            if s >= max_len:
                return node
            padw = [(0, 0)] * node["c"].ndim
            padw[-2] = (0, max_len - s)
            return {"c": jnp.pad(node["c"], padw),
                    "k_pe": jnp.pad(node["k_pe"], padw),
                    "length": node["length"]}
        if isinstance(node, dict) and "k" in node and "length" in node:
            s = node["k"].shape[-3]
            if s >= max_len:
                return node
            padw = [(0, 0)] * node["k"].ndim
            padw[-3] = (0, max_len - s)
            return {
                "k": jnp.pad(node["k"], padw),
                "v": jnp.pad(node["v"], padw),
                "length": node["length"],
            }
        if isinstance(node, dict):
            return {k: rec(v) for k, v in node.items()}
        if isinstance(node, list):
            return [rec(v) for v in node]
        return node

    return rec(cache)
