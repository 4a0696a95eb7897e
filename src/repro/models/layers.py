"""Shared neural-net layers: norms, RoPE, GQA / MLA attention, MLPs.

Parameters are plain nested dicts.  ``*_init(key, cfg, ...)`` builds one
layer's params; stacks vmap these over layer keys to produce scanned (L, ...)
pytrees.  All matmuls run in the param dtype with fp32 softmax/norm accum.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models.config import AttentionConfig, ModelConfig


def _he(key, shape, dtype, fan_in=None):
    fan_in = fan_in if fan_in is not None else shape[0]
    return (jax.random.normal(key, shape, jnp.float32)
            * (1.0 / np.sqrt(fan_in))).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def norm_init(d: int, kind: str, dtype):
    if kind == "layer":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    return {"scale": jnp.ones((d,), dtype)}


def apply_norm(p, x, kind: str, eps: float = 1e-6):
    if kind == "layer":
        xf = x.astype(jnp.float32)
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        return (y * p["scale"].astype(jnp.float32)
                + p["bias"].astype(jnp.float32)).astype(x.dtype)
    return ops.rmsnorm(x, p["scale"], eps=eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)


def apply_rope(x, positions, theta: float):
    """x: (..., S, D) with D even; positions: (S,) or (B, S)."""
    D = x.shape[-1]
    inv = rope_freqs(D, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * inv  # (..., S, D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    # broadcast (S, D/2) or (B, S, D/2) against (..., S, D/2)
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = x[..., ::2].astype(jnp.float32), x[..., 1::2].astype(jnp.float32)
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    return jnp.stack([r1, r2], axis=-1).reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------

def attention_init(key, d_model: int, a: AttentionConfig, dtype):
    ks = jax.random.split(key, 4)
    vd = a.v_dim
    return {
        "wq": _he(ks[0], (d_model, a.n_heads * a.head_dim), dtype),
        "wk": _he(ks[1], (d_model, a.n_kv_heads * a.head_dim), dtype),
        "wv": _he(ks[2], (d_model, a.n_kv_heads * vd), dtype),
        "wo": _he(ks[3], (a.n_heads * vd, d_model), dtype,
                  fan_in=a.n_heads * vd),
    }


def attention_fwd(p, x, a: AttentionConfig, *, positions, cache=None,
                  cache_len=None, causal=None):
    """x: (B, S, d).  cache: dict(k,v: (B, Smax, Hkv, D)) updated in decode.

    Returns (out, new_cache).  In prefill mode (cache given, S>1) the K/V are
    written at positions [0, S); in decode (S==1) at position cache_len.
    """
    B, S, _ = x.shape
    H, Hkv, D, vd = a.n_heads, a.n_kv_heads, a.head_dim, a.v_dim
    causal = a.causal if causal is None else causal
    q = (x @ p["wq"]).reshape(B, S, H, D)
    k = (x @ p["wk"]).reshape(B, S, Hkv, D)
    v = (x @ p["wv"]).reshape(B, S, Hkv, vd)
    q = apply_rope(q.swapaxes(1, 2), positions, a.rope_theta)   # (B,H,S,D)
    k = apply_rope(k.swapaxes(1, 2), positions, a.rope_theta)   # (B,Hkv,S,D)
    v = v.swapaxes(1, 2)

    if cache is None:
        o = ops.flash_attention(q, k, v, causal=causal,
                                sliding_window=a.sliding_window)
        new_cache = None
    elif S == 1:  # decode
        idx = cache_len  # scalar int32
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k.swapaxes(1, 2).astype(cache["k"].dtype),
            (0, idx, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], v.swapaxes(1, 2).astype(cache["v"].dtype),
            (0, idx, 0, 0))
        o = ops.decode_attention(
            q, k_cache.swapaxes(1, 2), v_cache.swapaxes(1, 2), cache_len + 1,
            sliding_window=a.sliding_window)
        new_cache = {"k": k_cache, "v": v_cache}
    else:  # prefill into cache
        o = ops.flash_attention(q, k, v, causal=causal,
                                sliding_window=a.sliding_window)
        Smax = cache["k"].shape[1]
        kp = jnp.pad(k.swapaxes(1, 2), ((0, 0), (0, Smax - S), (0, 0), (0, 0)))
        vp = jnp.pad(v.swapaxes(1, 2), ((0, 0), (0, Smax - S), (0, 0), (0, 0)))
        new_cache = {"k": kp.astype(cache["k"].dtype),
                     "v": vp.astype(cache["v"].dtype)}
    o = o.swapaxes(1, 2).reshape(B, S, H * vd)
    return o @ p["wo"], new_cache


def paged_attention_fwd(p, x, a: AttentionConfig, *, pages, page_table,
                        seq_lens, layer):
    """Decode one token per slot against a paged KV pool (continuous
    batching).  x: (B, 1, d); pages: dict(k/v: (L, n_pages, page, Hkv,
    D|Dv)), every layer's pool stacked; page_table: (B, maxp) int32;
    seq_lens: (B,) int32 — tokens already cached per slot; layer: () int32.
    The new token's K/V is written into pool ``layer`` at position
    ``seq_lens[b]`` (its page must already be allocated in the table), then
    the slot attends over ``seq_lens + 1`` entries — the exact analogue of
    the dense decode branch in ``attention_fwd``, with per-slot positions
    instead of one scalar ``cache_len``.  Returns (out, new_pages): the
    stacked pools with this layer's rows written, in place where ``pages``
    is a loop carry."""
    B, S, _ = x.shape
    H, Hkv, D, vd = a.n_heads, a.n_kv_heads, a.head_dim, a.v_dim
    q = (x @ p["wq"]).reshape(B, S, H, D)
    k = (x @ p["wk"]).reshape(B, S, Hkv, D)
    v = (x @ p["wv"]).reshape(B, S, Hkv, vd)
    positions = seq_lens[:, None]                            # (B, 1) absolute
    q = apply_rope(q.swapaxes(1, 2), positions, a.rope_theta)  # (B,H,1,D)
    k = apply_rope(k.swapaxes(1, 2), positions, a.rope_theta)  # (B,Hkv,1,D)
    v = v.swapaxes(1, 2)

    page = pages["k"].shape[2]
    # each slot's write position; inactive slots (their table rows all
    # point at the reserved trash page 0) scatter harmlessly
    ids = page_table[jnp.arange(B), seq_lens // page]
    rows = seq_lens % page
    k_pool = pages["k"].at[layer, ids, rows].set(
        k[:, :, 0].astype(pages["k"].dtype))
    v_pool = pages["v"].at[layer, ids, rows].set(
        v[:, :, 0].astype(pages["v"].dtype))
    o = ops.paged_attention(q, k_pool, v_pool, page_table, seq_lens + 1,
                            layer)
    o = o.swapaxes(1, 2).reshape(B, S, H * vd)
    return o @ p["wo"], {"k": k_pool, "v": v_pool}


def attention_cache_spec(a: AttentionConfig, batch: int, smax: int, dtype):
    return {"k": (batch, smax, a.n_kv_heads, a.head_dim),
            "v": (batch, smax, a.n_kv_heads, a.v_dim)}


# ---------------------------------------------------------------------------
# Multi-head Latent Attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_init(key, d_model: int, a: AttentionConfig, dtype):
    ks = jax.random.split(key, 6)
    H, Dn, Dr, Dv = a.n_heads, a.head_dim, a.qk_rope_head_dim, a.v_dim
    return {
        "wq_a": _he(ks[0], (d_model, a.q_lora_rank), dtype),
        "q_norm": jnp.ones((a.q_lora_rank,), dtype),
        "wq_b": _he(ks[1], (a.q_lora_rank, H * (Dn + Dr)), dtype),
        "wkv_a": _he(ks[2], (d_model, a.kv_lora_rank + Dr), dtype),
        "kv_norm": jnp.ones((a.kv_lora_rank,), dtype),
        "wk_b": _he(ks[3], (a.kv_lora_rank, H * Dn), dtype),
        "wv_b": _he(ks[4], (a.kv_lora_rank, H * Dv), dtype),
        "wo": _he(ks[5], (H * Dv, d_model), dtype, fan_in=H * Dv),
    }


def mla_fwd(p, x, a: AttentionConfig, *, positions, cache=None, cache_len=None):
    """MLA forward.  cache: dict(c_kv: (B,Smax,R), k_rope: (B,Smax,Dr))."""
    B, S, _ = x.shape
    H, Dn, Dr, Dv, R = (a.n_heads, a.head_dim, a.qk_rope_head_dim,
                        a.v_dim, a.kv_lora_rank)
    scale = 1.0 / np.sqrt(Dn + Dr)
    cq = ops.rmsnorm(x @ p["wq_a"], p["q_norm"])
    q = (cq @ p["wq_b"]).reshape(B, S, H, Dn + Dr)
    q_nope, q_rope = q[..., :Dn], q[..., Dn:]
    q_rope = apply_rope(q_rope.swapaxes(1, 2), positions, a.rope_theta)  # (B,H,S,Dr)

    kv_a = x @ p["wkv_a"]
    c_kv = ops.rmsnorm(kv_a[..., :R], p["kv_norm"])          # (B,S,R)
    k_rope = apply_rope(kv_a[..., None, R:].swapaxes(1, 2),
                        positions, a.rope_theta)              # (B,1,S,Dr)

    if cache is not None and S == 1:
        # ---- absorbed decode: score against the compressed cache ----
        idx = cache_len
        c_cache = jax.lax.dynamic_update_slice(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, idx, 0))
        r_cache = jax.lax.dynamic_update_slice(
            cache["k_rope"], k_rope[:, 0].astype(cache["k_rope"].dtype),
            (0, idx, 0))
        wk_b = p["wk_b"].reshape(R, H, Dn)
        q_abs = jnp.einsum("bshd,rhd->bhsr", q_nope, wk_b)   # (B,H,1,R)
        s = (jnp.einsum("bhsr,btr->bhst", q_abs.astype(jnp.float32),
                        c_cache.astype(jnp.float32))
             + jnp.einsum("bhsd,btd->bhst", q_rope.astype(jnp.float32),
                          r_cache.astype(jnp.float32))) * scale
        pos = jnp.arange(c_cache.shape[1])
        s = jnp.where((pos < cache_len + 1)[None, None, None], s, ops.NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        o_c = jnp.einsum("bhst,btr->bhsr", w, c_cache.astype(jnp.float32))
        wv_b = p["wv_b"].reshape(R, H, Dv)
        o = jnp.einsum("bhsr,rhd->bshd", o_c.astype(x.dtype), wv_b)
        new_cache = {"c_kv": c_cache, "k_rope": r_cache}
    else:
        # ---- train / prefill: materialize per-head K, V ----
        k_nope = (c_kv @ p["wk_b"]).reshape(B, S, H, Dn).swapaxes(1, 2)
        v = (c_kv @ p["wv_b"]).reshape(B, S, H, Dv).swapaxes(1, 2)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, H, S, Dr))], axis=-1)
        qq = jnp.concatenate([q_nope.swapaxes(1, 2), q_rope], axis=-1)
        o = ops.flash_attention(qq, k, v, causal=True, scale=scale)
        o = o.swapaxes(1, 2)
        if cache is not None:
            Smax = cache["c_kv"].shape[1]
            new_cache = {
                "c_kv": jnp.pad(c_kv, ((0, 0), (0, Smax - S), (0, 0))
                                ).astype(cache["c_kv"].dtype),
                "k_rope": jnp.pad(k_rope[:, 0], ((0, 0), (0, Smax - S), (0, 0))
                                  ).astype(cache["k_rope"].dtype),
            }
        else:
            new_cache = None
    out = o.reshape(B, S, H * Dv) @ p["wo"]
    return out, new_cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, gated: bool, dtype):
    ks = jax.random.split(key, 3)
    p = {"w_up": _he(ks[0], (d_model, d_ff), dtype),
         "w_down": _he(ks[1], (d_ff, d_model), dtype, fan_in=d_ff)}
    if gated:
        p["w_gate"] = _he(ks[2], (d_model, d_ff), dtype)
    return p


def mlp_fwd(p, x, act: str, gated: bool):
    h = x @ p["w_up"]
    if gated:
        g = x @ p["w_gate"]
        g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
        h = g * h
    else:
        h = jax.nn.gelu(h) if act == "gelu" else jax.nn.silu(h)
    return h @ p["w_down"]
