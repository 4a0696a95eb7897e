"""Plain reference of a llama-architecture decoder (DeepSeek LLM 7B): the
forward pass, computed a layer at a time so that it fits one chip beside
nothing else, with the weights drawn from the seed layer by layer."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.common import (einsum, he, mm, model_key, ones,
                                    rmsnorm, to_bf16)


def dims(cfg):
    d = cfg["hidden_size"]
    H = cfg["num_attention_heads"]
    return d, H, cfg["num_key_value_heads"], d // H, cfg["intermediate_size"]


def _keys(cfg, seed):
    k_emb, k_layers, _k_extra, k_head, _k_mask = jax.random.split(
        model_key(seed), 5)
    return k_emb, jax.random.split(k_layers, cfg["num_hidden_layers"]), k_head


def layer_weights(cfg, key):
    d, H, Hkv, D, ff = dims(cfg)
    k_attn, k_mlp = jax.random.split(key)
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    return {"wq": he(ka[0], (d, H * D)), "wk": he(ka[1], (d, Hkv * D)),
            "wv": he(ka[2], (d, Hkv * D)),
            "wo": he(ka[3], (H * D, d), fan_in=H * D),
            "w_up": he(km[0], (d, ff)), "w_down": he(km[1], (ff, d), fan_in=ff),
            "w_gate": he(km[2], (d, ff))}


def rope(x, theta: float):
    """x: (B, T, H, D), positions 0..T-1, pairs (2i, 2i+1) rotated."""
    T, D = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # (T, D/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("cfg_items", "prec"))
def _layer(x, key, cfg_items, prec):
    cfg = dict(cfg_items)
    d, H, Hkv, D, _ = dims(cfg)
    w = layer_weights(cfg, key)
    B, T, _ = x.shape
    h = rmsnorm(x, ones(d), cfg["rms_norm_eps"])
    q = rope(mm(h, w["wq"], prec).reshape(B, T, H, D), cfg["rope_theta"])
    k = rope(mm(h, w["wk"], prec).reshape(B, T, Hkv, D), cfg["rope_theta"])
    v = mm(h, w["wv"], prec).reshape(B, T, Hkv, D)
    k = jnp.repeat(k, H // Hkv, axis=2)
    v = jnp.repeat(v, H // Hkv, axis=2)
    s = einsum("bqhd,bkhd->bhqk", q, k, prec) / np.sqrt(D)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = einsum("bhqk,bkhd->bqhd", p, v, prec).reshape(B, T, H * D)
    x = x + mm(o, w["wo"], prec)
    h = rmsnorm(x, ones(d), cfg["rms_norm_eps"])
    g = jax.nn.silu(mm(h, w["w_gate"], prec)) * mm(h, w["w_up"], prec)
    return x + mm(g, w["w_down"], prec)


@functools.partial(jax.jit, static_argnames=("cfg_items", "prec"))
def _head(x, idx, key, cfg_items, prec):
    """Logits at the positions ``idx`` (B, N) of the final hidden states."""
    cfg = dict(cfg_items)
    d = cfg["hidden_size"]
    xs = jnp.take_along_axis(x, idx[..., None], axis=1)
    xs = rmsnorm(xs, ones(d), cfg["rms_norm_eps"])
    return mm(xs, he(key, (d, cfg["vocab_size"])), prec)


@functools.partial(jax.jit, static_argnames=("cfg_items",))
def _embed(tokens, key, cfg_items):
    cfg = dict(cfg_items)
    table = (jax.random.normal(key, (cfg["vocab_size"], cfg["hidden_size"]),
                               jnp.float32) * 0.02)
    return to_bf16(table)[tokens]


def model_cfg(cfg):
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "rms_norm_eps", "rope_theta", "vocab_size",
            "num_hidden_layers")
    return tuple((k, cfg[k]) for k in keys)


def logits_at(cfg, seed: int, tokens: np.ndarray, idx: np.ndarray,
              prec: str = "f32"):
    """Logits (B, N, V) at positions ``idx`` (B, N) of the sequences
    ``tokens`` (B, T), causal, every sequence from position 0.  Rows that
    are padding past a sequence's end never reach an earlier position."""
    items = model_cfg(cfg)
    k_emb, layer_keys, k_head = _keys(cfg, seed)
    x = _embed(jnp.asarray(tokens), k_emb, items)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_keys[i], items, prec)
    return _head(x, jnp.asarray(idx), k_head, items, prec)
