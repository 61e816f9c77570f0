"""GPipe-style pipeline parallelism over the ``pod`` mesh axis.

The multi-pod mesh (2, 16, 16) can drive its pod axis either as extra
data parallelism (default) or as pipeline stages (--pipeline). Here the
layer stack is split into ``n_stages`` contiguous stages; microbatches
flow through a ``shard_map`` loop of ``n_mb + n_stages - 1`` ticks with
``ppermute`` handoffs — the classic GPipe schedule, expressed so that
jax.grad differentiates straight through it (ppermute's transpose is
the reverse permute, giving the backward pipeline for free).

Embedding runs on stage 0, the LM head + loss on the last stage. The
loss leaves the body as a rank-1 per-stage ``P(stage_axis)`` output
summed *outside* — only the last stage contributes a nonzero partial,
so no in-body psum/broadcast collective is needed at all. Bubble
fraction is (n_stages - 1) / (n_mb + n_stages - 1) — the §Perf log
reasons about it explicitly.

This path implements the dense family (llama/qwen/gemma-style blocks);
it exists to prove the schedule and to give the dry-run a pipelined
multi-pod cell, not to replace the default DP-over-pod mapping.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from ..models.decoder import _attn_mlp_block, layer_metadata
from ..models.layers import embed_tokens, rmsnorm, unembed
from ..models.zoo import softmax_xent

__all__ = ["make_gpipe_loss"]


def make_gpipe_loss(cfg, mesh, *, n_stages: int, n_microbatches: int,
                    stage_axis: str = "pod", remat: bool = True):
    """Returns loss_fn(params, batch) running the GPipe schedule.

    params: the normal dense decoder tree (layers stacked (L, ...)).
    batch: {"tokens": (B, S), "labels": (B, S)}; B % n_microbatches == 0.
    The caller shards params' layer stacks over ``stage_axis`` via
    stage_param_sharding (stage dim = leading layer dim grouped).
    """
    assert cfg.family in ("dense",), "pipeline path implements dense archs"
    L = cfg.n_layers
    assert L % n_stages == 0, (L, n_stages)
    per_stage = L // n_stages
    win_all, theta_all = layer_metadata(cfg)

    def stage_fwd(stage_params, x, wins, thetas):
        def body(carry, xs):
            lp, w, th = xs
            y, _ = _attn_mlp_block(
                lp, carry, cfg, mode="train", cache=None, window=w, theta=th
            )
            return y, None

        b = jax.checkpoint(body) if remat else body
        x, _ = jax.lax.scan(b, x, (stage_params, wins, thetas))
        return x

    def loss_fn(params, batch):
        n_mb = n_microbatches
        tokens, labels = batch["tokens"], batch["labels"]
        b, s = tokens.shape
        assert b % n_mb == 0
        mb = b // n_mb
        tokens_mb = tokens.reshape(n_mb, mb, s)
        labels_mb = labels.reshape(n_mb, mb, s)

        # reshape layer stacks to (stages, per_stage, ...)
        layers = jax.tree.map(
            lambda a: a.reshape(n_stages, per_stage, *a.shape[1:]),
            params["layers"],
        )
        wins = win_all.reshape(n_stages, per_stage)
        thetas = theta_all.reshape(n_stages, per_stage)

        other_axes = tuple(a for a in mesh.axis_names if a != stage_axis)

        @functools.partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(
                P(stage_axis),  # layers: stage dim sharded
                P(stage_axis),  # wins
                P(stage_axis),  # thetas
                P(),  # embed/head/final norm: replicated
                P(None, None, None),  # tokens_mb
                P(None, None, None),  # labels_mb
            ),
            # per-stage loss partials; only the last stage's is nonzero
            out_specs=P(stage_axis),
        )
        def run(layers_s, wins_s, thetas_s, shared, toks, labs):
            my = jax.lax.axis_index(stage_axis)
            lp = jax.tree.map(lambda a: a[0], layers_s)  # local stage params
            w_l, t_l = wins_s[0], thetas_s[0]
            emb, fin = shared["embed"], shared["final_norm"]

            n_ticks = n_mb + n_stages - 1
            compute_dtype = jnp.dtype(cfg.compute_dtype)
            act0 = jnp.zeros((mb, s, cfg.d_model), compute_dtype)
            loss0 = jnp.zeros((1,), jnp.float32)
            fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

            def tick(carry, t):
                act, loss_sum = carry
                # stage 0 ingests microbatch t (if valid)
                mb_idx = jnp.clip(t, 0, n_mb - 1)
                x_in = embed_tokens(emb, toks[mb_idx], compute_dtype)
                x = jnp.where(my == 0, x_in, act)
                y = stage_fwd(lp, x, w_l, t_l)
                # last stage: loss for microbatch t - (n_stages - 1)
                out_idx = t - (n_stages - 1)
                valid_out = (out_idx >= 0) & (out_idx < n_mb)
                lab = labs[jnp.clip(out_idx, 0, n_mb - 1)]
                z = rmsnorm(y, fin, cfg.norm_eps)
                logits = unembed(emb, z, cfg.tie_embeddings)
                mb_loss = softmax_xent(logits, lab)
                is_last = my == n_stages - 1
                loss_sum = loss_sum + jnp.where(
                    is_last & valid_out, mb_loss, 0.0
                )[None]
                # hand activations forward
                act_next = jax.lax.ppermute(y, stage_axis, fwd_perm)
                return (act_next, loss_sum), None

            # carries become stage-varying after my-dependent selects
            act0_v = jax.lax.pcast(act0, (stage_axis,), to="varying")
            loss0_v = jax.lax.pcast(loss0, (stage_axis,), to="varying")
            (_, loss_sum), _ = jax.lax.scan(
                tick, (act0_v, loss0_v), jnp.arange(n_ticks)
            )
            return loss_sum

        shared = {"embed": params["embed"], "final_norm": params["final_norm"]}
        partials = run(layers, wins, thetas, shared, tokens_mb, labels_mb)
        # sum of per-stage partials == the last stage's loss; no
        # collective needed (stages other than the last contribute 0)
        return jnp.sum(partials) / n_mb

    return loss_fn
