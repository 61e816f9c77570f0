"""Mamba2 (SSD) block — the zamba2 backbone.

Structure (simplified from the Mamba2 paper; conv applies to the x
branch only, single B/C group):

  x -> in-projections: x_in, z (gate), B, C, dt
  x_in -> causal depthwise conv(width 4) -> silu
  y  = SSD-scan(u = dt*x_in, log-decay = dt*A_h, B, C) + D*x_in
  out = W_o (rmsnorm(y) * silu(z))

Train/prefill run the chunked kernel; decode advances the recurrence
one step carrying (ssm_state, conv_state).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.ssm_scan import ssm_scan
from ..kernels.ssm_scan.ref import ssm_step_ref
from .layers import proj, rmsnorm
from .params import ParamDef

__all__ = ["mamba_defs", "mamba_block", "mamba_init_state"]

_CONV_W = 4


def mamba_defs(cfg):
    e = cfg.d_model
    di = cfg.ssm_expand * e
    n = cfg.ssm_state
    h = di // cfg.ssm_head_dim
    return {
        "wx": ParamDef((e, di), contract=0),
        "wz": ParamDef((e, di), contract=0),
        "wB": ParamDef((e, n), contract=0),
        "wC": ParamDef((e, n), contract=0),
        "wdt": ParamDef((e, h), contract=0),
        "dt_bias": ParamDef((h,), init="zeros"),
        "A": ParamDef((h,), init="neg_linspace"),
        "D": ParamDef((h,), init="ones"),
        "conv_w": ParamDef((_CONV_W, di), init="normal", scale=0.5),
        "norm": ParamDef((di,), init="ones"),
        "wo": ParamDef((di, e), contract=0),
    }


def _causal_conv(x, w, state=None):
    """Depthwise causal conv, width 4. x: (B, S, Di); state: (B, 3, Di)
    carries the last 3 inputs for decode. Returns (y, new_state)."""
    b, s, di = x.shape
    pad = state if state is not None else jnp.zeros((b, _CONV_W - 1, di), x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)  # (B, S+3, Di)
    y = sum(
        xp[:, i : i + s, :] * w[i][None, None, :].astype(x.dtype)
        for i in range(_CONV_W)
    )
    new_state = xp[:, -(_CONV_W - 1) :, :]
    return y, new_state


def mamba_init_state(cfg, batch, dtype=jnp.float32):
    di = cfg.ssm_expand * cfg.d_model
    h = di // cfg.ssm_head_dim
    return {
        "ssm": jnp.zeros((batch, h, cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),
        "conv": jnp.zeros((batch, _CONV_W - 1, di), dtype),
    }


def mamba_block(p, x, cfg, *, mode: str, state=None):
    """Returns (y, new_state). state is required for decode; prefill
    returns the state for the decode loop."""
    b, s, e = x.shape
    di = cfg.ssm_expand * e
    hd = cfg.ssm_head_dim
    h = di // hd

    x_in = proj(x, p["wx"])  # (B, S, Di)
    z = proj(x, p["wz"])
    Bm = proj(x, p["wB"])  # (B, S, N)
    Cm = proj(x, p["wC"])
    dt = jax.nn.softplus(
        proj(x, p["wdt"]).astype(jnp.float32) + p["dt_bias"].astype(jnp.float32)
    )  # (B, S, H)

    conv_state = state["conv"] if state is not None else None
    x_c, new_conv = _causal_conv(x_in, p["conv_w"], conv_state)
    x_c = jax.nn.silu(x_c)
    xh = x_c.reshape(b, s, h, hd)

    A = p["A"].astype(jnp.float32)
    u = (dt[..., None] * xh.astype(jnp.float32)).astype(x.dtype)
    ld = dt * A[None, None, :]  # (B, S, H) log-decay
    Bh = jnp.broadcast_to(Bm[:, :, None, :], (b, s, h, cfg.ssm_state))
    Ch = jnp.broadcast_to(Cm[:, :, None, :], (b, s, h, cfg.ssm_state))

    if mode == "decode":
        assert state is not None and s == 1
        y1, new_ssm = ssm_step_ref(
            state["ssm"], u[:, 0], ld[:, 0], Bh[:, 0], Ch[:, 0]
        )
        y = y1[:, None]  # (B, 1, H, hd)
    else:
        y, new_ssm = ssm_scan(u, ld, Bh, Ch)

    y = y + p["D"].astype(x.dtype)[None, None, :, None] * xh
    y = y.reshape(b, s, di)
    y = rmsnorm(y, p["norm"], cfg.norm_eps) * jax.nn.silu(z)
    out = proj(y.astype(x.dtype), p["wo"])
    return out, {"ssm": new_ssm, "conv": new_conv}
