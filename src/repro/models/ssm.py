"""Recurrent sequence-mixing blocks: Mamba2 (SSD), mLSTM and sLSTM (xLSTM).

Each block exposes:
  *_init(key, d_model, cfg, dtype)          -> params
  *_fwd(p, x, cfg, *, state=None)           -> (y, new_state)
  *_state_spec(cfg, d_model, batch)         -> pytree of (shape, dtype)

``state=None`` means full-sequence (train/prefill) mode starting from zeros;
passing a state runs from it and returns the updated one (decode passes S=1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models.config import SSMConfig, XLSTMConfig
from repro.models.layers import _he


# ---------------------------------------------------------------------------
# causal depthwise conv (width W) with cached tail for decode
# ---------------------------------------------------------------------------

def causal_conv(x, w, tail=None):
    """x: (B, S, C); w: (W, C); tail: (B, W-1, C) previous inputs or None.

    Returns (y, new_tail).  y[t] = sum_i w[i] * x_ext[t + i] where x_ext is
    x left-padded with the tail (or zeros).
    """
    W = w.shape[0]
    B, S, C = x.shape
    if tail is None:
        tail = jnp.zeros((B, W - 1, C), x.dtype)
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)   # (B, S+W-1, C)
    y = sum(ext[:, i:i + S] * w[i].astype(x.dtype) for i in range(W))
    new_tail = ext[:, -(W - 1):] if W > 1 else tail
    return y, new_tail


# ===========================================================================
# Mamba2
# ===========================================================================

def mamba2_init(key, d_model: int, cfg: SSMConfig, dtype):
    di = cfg.expand * d_model
    H = di // cfg.head_dim
    N = cfg.state_dim
    conv_ch = di + 2 * N
    ks = jax.random.split(key, 4)
    return {
        # order: [z(di), x(di), B(N), C(N), dt(H)]
        "w_in": _he(ks[0], (d_model, 2 * di + 2 * N + H), dtype),
        "conv_w": _he(ks[1], (cfg.conv_width, conv_ch), dtype, fan_in=cfg.conv_width),
        "A_log": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm": jnp.ones((di,), dtype),
        "w_out": _he(ks[2], (di, d_model), dtype, fan_in=di),
    }


def mamba2_fwd(p, x, cfg: SSMConfig, d_model: int, *, state=None):
    B, S, _ = x.shape
    di = cfg.expand * d_model
    H = di // cfg.head_dim
    N = cfg.state_dim
    zxbcdt = x @ p["w_in"]
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * N]
    dt = zxbcdt[..., -H:]

    conv_tail = None if state is None else state["conv"]
    xbc, new_tail = causal_conv(xbc, p["conv_w"], conv_tail)
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(B, S, H, cfg.head_dim)
    Bm = xbc[..., di:di + N]
    Cm = xbc[..., di + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])

    h0 = None if state is None else state["ssm"]
    if S == 1 and state is not None:
        y, h = ops.ssd_decode_step(xs[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0],
                                   p["D"], h0)
        y = y[:, None]
    else:
        y, h = ops.ssd_scan(xs, dt, A, Bm, Cm, p["D"], chunk=cfg.chunk, h0=h0)
    y = y.reshape(B, S, di)
    y = ops.rmsnorm(y, p["norm"]) * jax.nn.silu(z)
    out = y @ p["w_out"]
    new_state = {"conv": new_tail, "ssm": h}
    return out, new_state


def mamba2_state_spec(cfg: SSMConfig, d_model: int, batch: int):
    di = cfg.expand * d_model
    H = di // cfg.head_dim
    return {"conv": ((batch, cfg.conv_width - 1, di + 2 * cfg.state_dim),
                     jnp.bfloat16),
            "ssm": ((batch, H, cfg.head_dim, cfg.state_dim), jnp.float32)}


# ===========================================================================
# mLSTM block (xLSTM)
# ===========================================================================

def _mlstm_dims(d_model: int, cfg: XLSTMConfig):
    inner = int(cfg.proj_factor * d_model)
    qk_total = int(cfg.qk_factor * inner)
    H = cfg.n_heads
    return inner, qk_total // H, inner // H, H   # inner, Dk, Dv, H


def mlstm_init(key, d_model: int, cfg: XLSTMConfig, dtype):
    inner, Dk, Dv, H = _mlstm_dims(d_model, cfg)
    ks = jax.random.split(key, 7)
    return {
        "w_up": _he(ks[0], (d_model, 2 * inner), dtype),
        "conv_w": _he(ks[1], (4, inner), dtype, fan_in=4),
        "wq": _he(ks[2], (inner, H * Dk), dtype, fan_in=inner),
        "wk": _he(ks[3], (inner, H * Dk), dtype, fan_in=inner),
        "wv": _he(ks[4], (inner, H * Dv), dtype, fan_in=inner),
        "w_if": _he(ks[5], (inner, 2 * H), dtype, fan_in=inner),
        "out_norm": jnp.ones((inner,), dtype),
        "w_down": _he(ks[6], (inner, d_model), dtype, fan_in=inner),
    }


def mlstm_fwd(p, x, cfg: XLSTMConfig, d_model: int, *, state=None):
    B, S, _ = x.shape
    inner, Dk, Dv, H = _mlstm_dims(d_model, cfg)
    up = x @ p["w_up"]
    xm, z = up[..., :inner], up[..., inner:]
    conv_tail = None if state is None else state["conv"]
    xc, new_tail = causal_conv(xm, p["conv_w"], conv_tail)
    xc = jax.nn.silu(xc)
    q = (xc @ p["wq"]).reshape(B, S, H, Dk).swapaxes(1, 2)
    k = (xc @ p["wk"]).reshape(B, S, H, Dk).swapaxes(1, 2)
    v = (xm @ p["wv"]).reshape(B, S, H, Dv).swapaxes(1, 2)
    gates = (xc @ p["w_if"]).reshape(B, S, 2, H)
    ig = gates[:, :, 0].swapaxes(1, 2)      # (B,H,S)
    fg = gates[:, :, 1].swapaxes(1, 2)

    carry = None if state is None else state["mlstm"]
    if S == 1 and state is not None:
        h, new_carry = ops.mlstm_decode_step(q[:, :, 0], k[:, :, 0], v[:, :, 0],
                                             ig[:, :, 0], fg[:, :, 0], carry)
        h = h[:, :, None]
    else:
        h, new_carry = ops.mlstm_scan(q, k, v, ig, fg, chunk=cfg.chunk,
                                      carry=carry)
    h = h.swapaxes(1, 2).reshape(B, S, inner)
    h = ops.rmsnorm(h, p["out_norm"]) * jax.nn.silu(z)
    out = h @ p["w_down"]
    return out, {"conv": new_tail, "mlstm": new_carry}


def mlstm_state_spec(cfg: XLSTMConfig, d_model: int, batch: int):
    inner, Dk, Dv, H = _mlstm_dims(d_model, cfg)
    return {"conv": ((batch, 3, inner), jnp.bfloat16),
            "mlstm": (((batch, H, Dk, Dv), jnp.float32),
                      ((batch, H, Dk), jnp.float32),
                      ((batch, H), jnp.float32))}


# ===========================================================================
# sLSTM block (xLSTM scalar memory, true recurrence)
# ===========================================================================

def slstm_init(key, d_model: int, cfg: XLSTMConfig, dtype):
    H = cfg.n_heads
    Dh = d_model // H
    ks = jax.random.split(key, 4)
    ff = int(d_model * 4 / 3)
    return {
        "w_gates": _he(ks[0], (d_model, 4 * d_model), dtype),      # z i f o
        "r_gates": _he(ks[1], (H, Dh, 4 * Dh), dtype, fan_in=Dh),  # block-diag
        "out_norm": jnp.ones((d_model,), dtype),
        "w_ff_gate": _he(ks[2], (d_model, ff), dtype),
        "w_ff_up": _he(ks[2], (d_model, ff), dtype),
        "w_ff_down": _he(ks[3], (ff, d_model), dtype, fan_in=ff),
    }


#: time steps per trip of the sLSTM loop: the bf16 sublane tile, so each
#: trip's (B, T, 4*d_model) gate slab and (B, T, d_model) output slab are
#: whole tiles.  A shorter sequence, or the last S mod T steps, run one
#: step per trip.
SLSTM_CHUNK = 16


def slstm_fwd(p, x, cfg: XLSTMConfig, d_model: int, *, state=None):
    """The recurrence runs S // SLSTM_CHUNK trips of SLSTM_CHUNK unrolled
    steps, then the S mod SLSTM_CHUNK steps left one per trip.  The chunked
    gate input is the scan's ``xs``, batch-major within a trip, so the
    forward reads and the backward writes it one aligned chunk per trip
    (as a loop constant its cotangent would be a sequence-sized buffer
    carried through every trip)."""
    B, S, _ = x.shape
    H = cfg.n_heads
    Dh = d_model // H
    T = SLSTM_CHUNK
    n_chunks, S_c = S // T, S // T * T

    if state is None:
        h0 = jnp.zeros((B, H, Dh), jnp.float32)
        c0 = jnp.zeros((B, H, Dh), jnp.float32)
        n0 = jnp.ones((B, H, Dh), jnp.float32)
        m0 = jnp.zeros((B, H, Dh), jnp.float32)
    else:
        h0, c0, n0, m0 = state["slstm"]

    r = p["r_gates"].astype(jnp.float32)

    def step(carry, gx):
        h, c, n, m = carry                          # (B,H,Dh) each
        rec = jnp.einsum("bhd,hdg->bhg", h, r).reshape(B, H, 4, Dh)
        g = gx.astype(jnp.float32) + jnp.moveaxis(rec, 2, 1)
        # g: (B, 4, H, Dh) -> z i f o
        z_t = jnp.tanh(g[:, 0])
        i_t = g[:, 1]
        f_t = g[:, 2]
        o_t = jax.nn.sigmoid(g[:, 3])
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m, i_t)
        i_p = jnp.exp(i_t - m_new)
        f_p = jnp.exp(logf + m - m_new)
        c = f_p * c + i_p * z_t
        n = f_p * n + i_p
        h = o_t * c / jnp.maximum(jnp.abs(n), 1.0)
        # ys stacked in bf16: keeps the scan-carry buffer dtype-stable (no
        # full-buffer converts per trip) and halves the stacked-output HBM
        return (h, c, n, m_new), h.astype(jnp.bfloat16)

    # checkpointed: the backward keeps one incoming carry per trip and
    # recomputes the trip's steps, where saving each step's intermediates
    # would stack every one of them on its own and slice each per trip
    @functools.partial(jax.checkpoint, prevent_cse=False)
    def chunk(carry, gx):                           # gx: (B, T, 4*d_model)
        hs = []
        for t in range(T):
            carry, h = step(carry, gx[:, t].reshape(B, 4, H, Dh))
            hs.append(h.reshape(B, d_model))
        return carry, jnp.stack(hs, axis=1)        # (B, T, d_model)

    carry, ys = (h0, c0, n0, m0), []
    if n_chunks:
        # (B, S_c, d) -> (n_chunks, B, T, d) before the projection: moving
        # x's whole tiles is a quarter of moving its product's
        xc = x[:, :S_c].reshape(B, n_chunks, T, d_model).swapaxes(0, 1)
        carry, hs = jax.lax.scan(chunk, carry, xc @ p["w_gates"])
        ys.append(hs.swapaxes(0, 1).reshape(B, S_c, d_model))
    if S > S_c:
        gates_x = (x[:, S_c:] @ p["w_gates"]).reshape(B, S - S_c, 4, H, Dh)
        carry, hs = jax.lax.scan(step, carry, jnp.moveaxis(gates_x, 1, 0))
        ys.append(jnp.moveaxis(hs, 0, 1).reshape(B, S - S_c, d_model))
    hf, cf, nf, mf = carry
    y = jnp.concatenate(ys, axis=1).astype(x.dtype)
    y = ops.rmsnorm(y, p["out_norm"])
    ff = jax.nn.silu(y @ p["w_ff_gate"]) * (y @ p["w_ff_up"])
    out = ff @ p["w_ff_down"]
    return out, {"slstm": (hf, cf, nf, mf)}


def slstm_state_spec(cfg: XLSTMConfig, d_model: int, batch: int):
    H = cfg.n_heads
    Dh = d_model // H
    s = ((batch, H, Dh), jnp.float32)
    return {"slstm": (s, s, s, s)}
