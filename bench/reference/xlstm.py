"""Plain reference of the xLSTM[7:1] language model: its loss and gradient
(the AdamW steps are ``common.adamw``).

mLSTM in its parallel (quadratic) stabilized form, sLSTM as a step-by-step
recurrence, both in float32.  The weights are drawn from the seed with the
same key derivation and layout (layer groups of 7 mLSTM + 1 sLSTM, stacked)
as the configuration's model, so that each leaf can be compared by name."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import (einsum, he, mm, model_key, ones,
                                    rmsnorm, to_bf16)


def dims(cfg):
    d, H = cfg["hidden_size"], cfg["num_heads"]
    inner = int(cfg["proj_factor"] * d)
    qk = int(cfg["qk_factor"] * inner)
    return d, H, inner, qk // H, inner // H       # d, H, inner, Dk, Dv


def _mlstm_init(key, cfg):
    d, H, inner, Dk, Dv = dims(cfg)
    W = cfg["conv_width"]
    ks = jax.random.split(key, 7)
    return {"w_up": he(ks[0], (d, 2 * inner)),
            "conv_w": he(ks[1], (W, inner), fan_in=W),
            "wq": he(ks[2], (inner, H * Dk), fan_in=inner),
            "wk": he(ks[3], (inner, H * Dk), fan_in=inner),
            "wv": he(ks[4], (inner, H * Dv), fan_in=inner),
            "w_if": he(ks[5], (inner, 2 * H), fan_in=inner),
            "out_norm": ones(inner),
            "w_down": he(ks[6], (inner, d), fan_in=inner)}


def _slstm_init(key, cfg):
    d, H = cfg["hidden_size"], cfg["num_heads"]
    Dh = d // H
    ff = int(d * cfg["slstm_ff_factor"])
    ks = jax.random.split(key, 4)
    return {"w_gates": he(ks[0], (d, 4 * d)),
            "r_gates": he(ks[1], (H, Dh, 4 * Dh), fan_in=Dh),
            "out_norm": ones(d),
            # gate and up projection are drawn from one key, as in the
            # configuration's model
            "w_ff_gate": he(ks[2], (d, ff)), "w_ff_up": he(ks[2], (d, ff)),
            "w_ff_down": he(ks[3], (ff, d), fan_in=ff)}


def _group_init(key, cfg):
    d = cfg["hidden_size"]
    n_m = cfg["slstm_every"] - 1
    keys = jax.random.split(key, n_m + 1)
    m = jax.vmap(lambda k: {"ln": {"scale": ones(d)},
                            "blk": _mlstm_init(k, cfg)})(keys[:n_m])
    s = {"ln": {"scale": ones(d)}, "blk": _slstm_init(keys[-1], cfg)}
    return {"mlstm": m, "slstm": s}


def init_params(cfg, seed: int):
    """The full parameter tree in float32 (values are bfloat16's)."""
    k_emb, k_layers, _, _, _ = jax.random.split(model_key(seed), 5)
    ng = cfg["num_hidden_layers"] // cfg["slstm_every"]
    layers = jax.vmap(lambda k: _group_init(k, cfg))(
        jax.random.split(k_layers, ng))
    embed = (jax.random.normal(k_emb, (cfg["vocab_size"], cfg["hidden_size"]),
                               jnp.float32) * 0.02)
    return {"layers": layers, "embed": to_bf16(embed),
            "final_norm": {"scale": ones(cfg["hidden_size"])}}


# ----------------------------------------------------------------- forward

def causal_conv(x, w):
    """y[t] = sum_i w[i] * x[t + i - (W - 1)], zeros before the start."""
    W = w.shape[0]
    S = x.shape[1]
    ext = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    return sum(ext[:, i:i + S] * w[i] for i in range(W))


def mlstm_parallel(q, k, v, ig, fg, prec):
    """Stabilized parallel mLSTM.  q, k: (B,H,S,Dk); v: (B,H,S,Dv);
    gates (B,H,S).  h_t = sum_j C_tj v_j / max(|sum_j C_tj|, exp(-m_t)),
    C_tj = q_t.k_j / sqrt(Dk) * exp(D_tj - m_t), D_tj = F_t - F_j + i_j
    (j <= t), F the cumulative log forget gate, m_t = max_j D_tj."""
    S = q.shape[2]
    F = jnp.cumsum(jax.nn.log_sigmoid(fg), axis=-1)
    Dm = F[..., :, None] - F[..., None, :] + ig[..., None, :]
    causal = jnp.tril(jnp.ones((S, S), bool))
    Dm = jnp.where(causal, Dm, -jnp.inf)
    m = jnp.max(Dm, axis=-1)
    s = einsum("bhtd,bhjd->bhtj", q, k, prec) / np.sqrt(q.shape[-1])
    C = s * jnp.exp(Dm - m[..., None])
    den = jnp.maximum(jnp.abs(C.sum(-1)), jnp.exp(-m))
    return einsum("bhtj,bhjv->bhtv", C, v, prec) / den[..., None]


def mlstm_block(p, x, cfg, prec):
    d, H, inner, Dk, Dv = dims(cfg)
    B, S, _ = x.shape
    up = mm(x, p["w_up"], prec)
    xm, z = up[..., :inner], up[..., inner:]
    xc = jax.nn.silu(causal_conv(xm, p["conv_w"]))
    heads = lambda t, n: t.reshape(B, S, H, n).swapaxes(1, 2)
    q = heads(mm(xc, p["wq"], prec), Dk)
    k = heads(mm(xc, p["wk"], prec), Dk)
    v = heads(mm(xm, p["wv"], prec), Dv)
    gates = mm(xc, p["w_if"], prec).reshape(B, S, 2, H)
    ig, fg = gates[:, :, 0].swapaxes(1, 2), gates[:, :, 1].swapaxes(1, 2)
    h = mlstm_parallel(q, k, v, ig, fg, prec)
    h = h.swapaxes(1, 2).reshape(B, S, inner)
    h = rmsnorm(h, p["out_norm"]) * jax.nn.silu(z)
    return mm(h, p["w_down"], prec)


def slstm_block(p, x, cfg, prec):
    d, H = cfg["hidden_size"], cfg["num_heads"]
    Dh = d // H
    B, S, _ = x.shape
    gx = mm(x, p["w_gates"], prec).reshape(B, S, 4, H, Dh)
    r = p["r_gates"]

    def step(carry, g_t):
        h, c, n, m = carry
        rec = einsum("bhd,hdg->bhg", h, r, prec).reshape(B, H, 4, Dh)
        g = g_t + jnp.moveaxis(rec, 2, 1)
        z, i, f, o = (jnp.tanh(g[:, 0]), g[:, 1], g[:, 2],
                      jax.nn.sigmoid(g[:, 3]))
        logf = jax.nn.log_sigmoid(f)
        m_new = jnp.maximum(logf + m, i)
        ip, fp = jnp.exp(i - m_new), jnp.exp(logf + m - m_new)
        c = fp * c + ip * z
        n = fp * n + ip
        h = o * c / jnp.maximum(jnp.abs(n), 1.0)
        return (h, c, n, m_new), h

    zero = jnp.zeros((B, H, Dh), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero, zero + 1.0, zero),
                         jnp.moveaxis(gx, 1, 0))
    y = rmsnorm(jnp.moveaxis(hs, 0, 1).reshape(B, S, d), p["out_norm"])
    ff = jax.nn.silu(mm(y, p["w_ff_gate"], prec)) * mm(y, p["w_ff_up"], prec)
    return mm(ff, p["w_ff_down"], prec)


def loss(params, tokens, labels, cfg, prec):
    """Mean next-token cross-entropy over every position of every row."""
    x = params["embed"][tokens]

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def m_layer(x, lp):
        return x + mlstm_block(lp["blk"], rmsnorm(x, lp["ln"]["scale"]),
                               cfg, prec), None

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def s_layer(x, lp):
        return x + slstm_block(lp["blk"], rmsnorm(x, lp["ln"]["scale"]),
                               cfg, prec)

    def group(x, gp):
        x, _ = jax.lax.scan(m_layer, x, gp["mlstm"])
        return s_layer(x, gp["slstm"]), None

    x, _ = jax.lax.scan(group, x, params["layers"])
    x = rmsnorm(x, params["final_norm"]["scale"])
    logits = mm(x, params["embed"].T, prec)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


@functools.partial(jax.jit, static_argnames=("cfg_items", "prec", "scale"))
def _chunk_grad(params, tokens, labels, cfg_items, prec, scale):
    cfg = dict(cfg_items)
    l, g = jax.value_and_grad(loss)(params, tokens, labels, cfg, prec)
    return l * scale, jax.tree.map(lambda t: t * scale, g)


def loss_and_grad(params, tokens, labels, cfg, prec="f32", rows: int = 2):
    """Whole-batch mean loss and gradient, summed over blocks of ``rows``."""
    B = tokens.shape[0]
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    total, grads = 0.0, None
    for r in range(0, B, rows):
        l, g = _chunk_grad(params, tokens[r:r + rows], labels[r:r + rows],
                           items, prec, rows / B)
        total = total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total, grads
