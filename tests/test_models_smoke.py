"""Per-arch smoke tests: reduced config, forward + loss gradient on
CPU, output shapes + finiteness; decode-vs-prefill cache consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY, reduced
from repro.models import build

ARCHS = sorted(REGISTRY)


def _batch(cfg, B=2, S=32, seed=0):
    rng = jax.random.PRNGKey(seed)
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab)
    batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
    if cfg.family == "vlm":
        batch["image_embeds"] = jax.random.normal(
            rng, (B, cfg.n_image_tokens, cfg.d_model))
    if cfg.family == "encdec":
        batch["enc_frames"] = jax.random.normal(rng, (B, cfg.enc_seq, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step(arch):
    """The loss and its gradient are finite, and the gradient is not
    zero: ``Model.loss`` is the reference a backward-pass lowering
    would be held against."""
    cfg = reduced(REGISTRY[arch])
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    loss, grads = jax.jit(jax.value_and_grad(model.loss))(params, _batch(cfg))
    assert jnp.isfinite(loss), arch
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves), arch
    assert sum(float(jnp.sum(jnp.abs(g))) for g in leaves) > 0, arch


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_matches_prefill(arch):
    """The strongest cache test: decode(token S) == prefill(S+1)[-1]."""
    cfg = reduced(REGISTRY[arch])
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(1))
    B, S = 2, 24
    batch = _batch(cfg, B, S + 1, seed=2)
    toks = batch["tokens"]
    extra = {k: v for k, v in batch.items() if k not in ("tokens", "labels")}
    full_logits, _ = model.prefill(params, {"tokens": toks, **extra}, max_len=S + 2)
    want = np.asarray(full_logits[:, S])
    _, cache = model.prefill(params, {"tokens": toks[:, :S], **extra}, max_len=S + 2)
    got_l, _ = model.decode(params, cache, {"token": toks[:, S:S + 1]})
    got = np.asarray(got_l[:, 0])
    err = np.abs(got - want).max() / (np.abs(want).max() + 1e-9)
    assert err < 5e-3, (arch, err)


def test_vocab_logit_shapes():
    for arch in ARCHS:
        cfg = reduced(REGISTRY[arch])
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = _batch(cfg, B=1, S=8)
        logits, _ = model.prefill(
            params, {k: v for k, v in batch.items() if k != "labels"}, max_len=16
        )
        assert logits.shape == (1, 8, cfg.vocab)
