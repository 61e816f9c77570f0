"""Model zoo facade: one `Model` object per architecture config.

The plain, unsharded reference the lowering (``core.network``) is held
against: traced by ``jax.make_jaxpr``, its weight GEMMs carry the
stream's names. One surface for every family:

    model = build(cfg)
    params = model.init(rng)
    loss   = model.loss(params, batch)            # train shapes
    logits, cache = model.prefill(params, batch)  # prefill shapes
    logits, cache = model.decode(params, cache, batch)  # one decode step
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..config import ArchConfig
from .decoder import decoder_defs, decoder_forward, init_cache
from .encdec import encdec_defs, encdec_forward, encdec_init_cache
from .moe import aux_load_balance_loss
from .params import abstract, materialize, tree_size

__all__ = ["Model", "build"]


def softmax_xent(logits, labels):
    """Mean next-token cross-entropy; logits f32 (B, S, V)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    defs: Any  # ParamDef tree

    # ---- parameters -----------------------------------------------------
    def init(self, rng, dtype=None):
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        return materialize(self.defs, rng, dtype)

    def abstract_params(self, dtype=None):
        dtype = dtype or jnp.dtype(self.cfg.param_dtype)
        return abstract(self.defs, dtype)

    @property
    def n_params(self) -> int:
        return tree_size(self.defs)

    # ---- forward ------------------------------------------------------------
    def _forward(self, params, tokens, *, mode, cache=None, batch=None,
                 max_len=0):
        cfg = self.cfg
        batch = batch or {}
        if cfg.family == "encdec":
            return encdec_forward(
                params, tokens, cfg, mode=mode,
                enc_frames=batch.get("enc_frames"), cache=cache,
                max_len=max_len,
            )
        return decoder_forward(
            params, tokens, cfg, mode=mode, cache=cache,
            image_embeds=batch.get("image_embeds"), max_len=max_len,
        )

    def loss(self, params, batch):
        logits, _ = self._forward(params, batch["tokens"], mode="train", batch=batch)
        loss = softmax_xent(logits, batch["labels"])
        if self.cfg.family == "moe":
            # load-balance aux on the first layer's router (cheap proxy)
            first = jax.tree.map(lambda a: a[0], params["layers"])
            x = jnp.take(params["embed"]["tok"], batch["tokens"], axis=0)
            loss = loss + 0.01 * aux_load_balance_loss(
                first["ffn"], x.astype(jnp.float32), self.cfg
            )
        return loss

    def prefill(self, params, batch, *, max_len: int = 0):
        return self._forward(
            params, batch["tokens"], mode="prefill", batch=batch,
            max_len=max_len,
        )

    def decode(self, params, cache, batch):
        """One serve step: batch["token"] (B, 1) -> logits (B, 1, V)."""
        return self._forward(params, batch["token"], mode="decode", cache=cache)

    # ---- caches -----------------------------------------------------------
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        if self.cfg.family == "encdec":
            return encdec_init_cache(self.cfg, batch, max_len, dtype)
        return init_cache(self.cfg, batch, max_len, dtype)

    def abstract_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        return jax.eval_shape(
            lambda: self.init_cache(batch, max_len, dtype)
        )


def build(cfg: ArchConfig) -> Model:
    if cfg.family == "encdec":
        defs = encdec_defs(cfg)
    else:
        defs = decoder_defs(cfg)
    return Model(cfg=cfg, defs=defs)
