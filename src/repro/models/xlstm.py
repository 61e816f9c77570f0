"""xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory).

mLSTM is expressed as two SSD scans (reusing the chunked kernel — the
same dOS "stationary state over sequential chunk-tiers" structure):

  C_t = f_t C_{t-1} + (i_t k_t) v_t^T       -> ssm_scan(u=v, ld=log f, B=i*k, C=q)
  n_t = f_t n_{t-1} + (i_t k_t)             -> ssm_scan(u=1, ...) with P=1
  y_t = (C_t^T q_t) / max(|n_t^T q_t|, 1)

The paper's technique (dOS / K-dim sharding) does NOT apply to the
recurrence itself — the memory update is an outer product (K = 1); it
applies only to the q/k/v/out projections. Recorded in DESIGN.md
§Arch-applicability.

sLSTM keeps per-head scalar state with a plain lax.scan (inherently
sequential, as the xLSTM paper states).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..kernels.ssm_scan import ssm_scan
from ..kernels.ssm_scan.ref import ssm_step_ref
from .layers import proj, rmsnorm
from .params import ParamDef

__all__ = [
    "mlstm_defs", "mlstm_block", "mlstm_init_state",
    "slstm_defs", "slstm_block", "slstm_init_state",
]


# --- mLSTM -------------------------------------------------------------------


def mlstm_defs(cfg):
    e = cfg.d_model
    h = cfg.n_heads
    n = cfg.ssm_state  # key/query dim per head
    p_ = cfg.ssm_head_dim  # value dim per head
    return {
        "wq": ParamDef((e, h * n), contract=0),
        "wk": ParamDef((e, h * n), contract=0),
        "wv": ParamDef((e, h * p_), contract=0),
        "wi": ParamDef((e, h), contract=0),
        "wf": ParamDef((e, h), contract=0),
        "bf": ParamDef((h,), init="ones"),
        "wo_gate": ParamDef((e, h * p_), contract=0),
        "norm": ParamDef((h * p_,), init="ones"),
        "wo": ParamDef((h * p_, e), contract=0),
    }


def mlstm_init_state(cfg, batch):
    h, n, p_ = cfg.n_heads, cfg.ssm_state, cfg.ssm_head_dim
    return {
        "C": jnp.zeros((batch, h, n, p_), jnp.float32),
        "n": jnp.zeros((batch, h, n, 1), jnp.float32),
    }


def mlstm_block(p, x, cfg, *, mode: str, state=None):
    b, s, e = x.shape
    h, n, p_ = cfg.n_heads, cfg.ssm_state, cfg.ssm_head_dim

    q = proj(x, p["wq"]).reshape(b, s, h, n)
    k = proj(x, p["wk"]).reshape(b, s, h, n) / (n**0.5)
    v = proj(x, p["wv"]).reshape(b, s, h, p_)
    i_pre = proj(x, p["wi"]).astype(jnp.float32)  # (B,S,H)
    f_pre = proj(x, p["wf"]).astype(jnp.float32) + p["bf"].astype(jnp.float32)

    # Stabilized exponential gating (xLSTM Sec. 2): fold the input gate
    # into B and keep log f as the decay.
    ld = jax.nn.log_sigmoid(f_pre)  # (B,S,H)
    i_gate = jnp.exp(jnp.minimum(i_pre, 10.0))  # clipped exp input gate
    Bk = (k.astype(jnp.float32) * i_gate[..., None]).astype(x.dtype)  # i_t * k_t

    ones = jnp.ones((b, s, h, 1), x.dtype)
    if mode == "decode":
        assert state is not None and s == 1
        yc, newC = ssm_step_ref(state["C"], v[:, 0], ld[:, 0], Bk[:, 0], q[:, 0])
        yn, newn = ssm_step_ref(state["n"], ones[:, 0], ld[:, 0], Bk[:, 0], q[:, 0])
        yc, yn = yc[:, None], yn[:, None]
        new_state = {"C": newC, "n": newn}
    else:
        yc, newC = ssm_scan(v, ld, Bk, q)  # (B,S,H,P)
        yn, newn = ssm_scan(ones, ld, Bk, q)  # (B,S,H,1)
        new_state = {"C": newC, "n": newn}

    denom = jnp.maximum(jnp.abs(yn.astype(jnp.float32)), 1.0)
    y = yc.astype(jnp.float32) / denom  # (B,S,H,P)
    y = y.reshape(b, s, h * p_)
    y = rmsnorm(y.astype(x.dtype), p["norm"], cfg.norm_eps)
    o_gate = jax.nn.sigmoid(proj(x, p["wo_gate"]).astype(jnp.float32))
    y = (y.astype(jnp.float32) * o_gate).astype(x.dtype)
    return proj(y, p["wo"]), new_state


# --- sLSTM --------------------------------------------------------------------


def slstm_defs(cfg):
    e = cfg.d_model
    h = cfg.n_heads
    d_h = e // h
    # recurrent weights are per-head block-diagonal (xLSTM's heads)
    return {
        "wz": ParamDef((e, e), contract=0),
        "wi": ParamDef((e, h), contract=0),
        "wf": ParamDef((e, h), contract=0),
        "wo_gate": ParamDef((e, e), contract=0),
        "bf": ParamDef((h,), init="ones"),
        "r": ParamDef((h, d_h, d_h), scale=0.1),
        "norm": ParamDef((e,), init="ones"),
        "wo": ParamDef((e, e), contract=0),
    }


def slstm_init_state(cfg, batch):
    e = cfg.d_model
    h = cfg.n_heads
    return {
        "c": jnp.zeros((batch, e), jnp.float32),
        "n": jnp.zeros((batch, h), jnp.float32),
        "h": jnp.zeros((batch, e), jnp.float32),
    }


def slstm_block(p, x, cfg, *, mode: str, state=None):
    """Scalar-memory LSTM with a recurrent (previous-output) term.
    Sequential over time by construction."""
    b, s, e = x.shape
    h = cfg.n_heads
    d_h = e // h

    z_in = proj(x, p["wz"]).astype(jnp.float32)
    i_in = proj(x, p["wi"]).astype(jnp.float32)
    f_in = proj(x, p["wf"]).astype(jnp.float32) + p["bf"].astype(jnp.float32)
    o_in = proj(x, p["wo_gate"]).astype(jnp.float32)
    r = p["r"].astype(jnp.float32)

    if state is None:
        state = slstm_init_state(cfg, b)

    def step(carry, inp):
        c, nrm, h_prev = carry
        z_t, i_t, f_t, o_t = inp
        # recurrent contribution from h_{t-1} (per-head block diagonal)
        hp = h_prev.reshape(b, h, d_h)
        rec = jnp.einsum("bhd,hde->bhe", hp, r).reshape(b, e)
        z = jnp.tanh(z_t + rec)
        i_g = jnp.exp(jnp.minimum(i_t, 10.0))  # (b, h)
        f_g = jax.nn.sigmoid(f_t)
        c_new = (
            jnp.repeat(f_g, d_h, axis=-1) * c + jnp.repeat(i_g, d_h, axis=-1) * z
        )
        n_new = f_g * nrm + i_g
        h_head = c_new.reshape(b, h, d_h) / jnp.maximum(n_new, 1.0)[..., None]
        o_g = jax.nn.sigmoid(o_t)
        h_new = (o_g * h_head.reshape(b, e))
        return (c_new, n_new, h_new), h_new

    inputs = (
        z_in.transpose(1, 0, 2),
        i_in.transpose(1, 0, 2),
        f_in.transpose(1, 0, 2),
        o_in.transpose(1, 0, 2),
    )
    (c, nrm, h_last), ys = jax.lax.scan(
        step, (state["c"], state["n"], state["h"]), inputs
    )
    y = ys.transpose(1, 0, 2).astype(x.dtype)  # (B,S,E)
    y = rmsnorm(y, p["norm"], cfg.norm_eps)
    return proj(y, p["wo"]), {"c": c, "n": nrm, "h": h_last}
