"""Mixture-of-Experts layer: top-k token-choice routing with per-data-shard
scatter/gather dispatch (production EP layout).

Routing and capacity are computed *per data shard* (leading DP dim), so
position-within-expert is a local cumsum — no cross-device sequential
dependency, unlike a global-T dispatch.  Tokens are scattered into
(DP, E, C, d) expert buffers (rows, no one-hot einsums: dispatch costs ~zero
flops); the reshard of those buffers from dp-sharded to expert(model)-sharded
is exactly the EP all-to-all.  The N shared experts are fused into one wide
MLP (concatenated ffs sum after the down-projection).

Token-choice semantics match the papers (per-token top-k); capacity/overflow
is per-shard, as deployed systems do.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import MoEConfig
from repro.models.layers import _he, mlp_fwd, mlp_init
from repro.sharding import ctx as shard_ctx


def moe_init(key, d_model: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(key, 5)
    E, F = cfg.n_experts, cfg.d_ff_expert
    p = {
        "router": _he(ks[0], (d_model, E), jnp.float32),
        "w_gate": _he(ks[1], (E, d_model, F), dtype, fan_in=d_model),
        "w_up": _he(ks[2], (E, d_model, F), dtype, fan_in=d_model),
        "w_down": _he(ks[3], (E, F, d_model), dtype, fan_in=F),
    }
    if cfg.n_shared > 0:
        p["shared"] = mlp_init(ks[4], d_model, cfg.n_shared * cfg.shared_ff,
                               gated=True, dtype=dtype)
    return p


def moe_fwd(p, x, cfg: MoEConfig, act: str = "silu"):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    T = B * S
    E, K = cfg.n_experts, cfg.top_k
    DP = shard_ctx.dp_size()
    if T % DP != 0:
        DP = 1
    Tl = T // DP

    xs = x.reshape(DP, Tl, d)
    logits = (xs.astype(jnp.float32) @ p["router"])          # (DP, Tl, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, idx = jax.lax.top_k(probs, K)                 # (DP, Tl, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # per-shard capacity (static)
    C = max(1, int(np.ceil(Tl * K / E * cfg.capacity_factor)))

    # position of each (token, k) within its expert — local cumsum per shard,
    # k-major priority (k=0 choices claim slots first)
    pos_k = []
    counts = jnp.zeros((DP, 1, E), jnp.int32)
    for j in range(K):
        oh = jax.nn.one_hot(idx[:, :, j], E, dtype=jnp.int32)   # (DP, Tl, E)
        pos_all = jnp.cumsum(oh, axis=1) - 1 + counts           # (DP, Tl, E)
        pos_k.append(jnp.take_along_axis(
            pos_all, idx[:, :, j:j + 1], axis=-1)[..., 0])      # (DP, Tl)
        counts = counts + oh.sum(axis=1, keepdims=True)

    # stack (token,k) choices: slot ids within the per-shard expert buffer
    OVERFLOW = E * C
    slot_k, weight_k = [], []
    for j in range(K):
        pos = pos_k[j]
        valid = pos < C
        slot_k.append(jnp.where(valid, idx[:, :, j] * C + pos, OVERFLOW))
        weight_k.append((gate_vals[:, :, j] * valid).astype(x.dtype))
    slots = jnp.stack(slot_k)                                # (K, DP, Tl)
    weights = jnp.stack(weight_k)                            # (K, DP, Tl)

    # scatter tokens into per-shard expert buffers (DP, E*C, d).  The SPMD
    # scatter partitioner cannot prove the batch-dim locality of this
    # scatter and falls back to replicate+all-reduce of the full buffer
    # (measured 3 TB/device/step fwd + 8.5 TB in bwd on deepseek-v2), so the
    # dispatch/combine run under shard_map: manual over dp, auto elsewhere.
    xs = shard_ctx.constrain_moe_shards(xs)
    buf = _shardmapped(_scatter_local, (xs, slots), E=E, C=C)
    ebuf = buf.reshape(DP, E, C, d)
    ebuf = shard_ctx.constrain_expert_buffers(ebuf)             # EP all-to-all
    g = jnp.einsum("secd,edf->secf", ebuf, p["w_gate"])
    u = jnp.einsum("secd,edf->secf", ebuf, p["w_up"])
    g = jax.nn.silu(g) if act == "silu" else jax.nn.gelu(g)
    h = jnp.einsum("secf,efd->secd", g * u, p["w_down"])
    h = shard_ctx.constrain_expert_buffers(h)
    hflat = shard_ctx.constrain_moe_shards(h.reshape(DP, E * C, d))  # to dp
    out = _shardmapped(_combine_local, (hflat, slots, weights), E=E, C=C)

    if cfg.n_shared > 0:
        out = out + mlp_fwd(p["shared"], xs, act, gated=True)

    # load-balancing auxiliary loss (Switch-style)
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32), axis=(0, 1))
    frac_probs = probs.mean((0, 1))
    aux = cfg.router_aux_coef * E * jnp.sum(frac_tokens * frac_probs)
    return out.reshape(B, S, d), aux


def _scatter_local(xs, slots, *, E, C):
    """Per-shard dispatch.  xs: (DPl, Tl, d); slots: (K, DPl, Tl) with
    overflow id E*C (out of bounds -> mode='drop' discards it without the
    concat+slice round-trip of an explicit overflow row)."""
    DPl, Tl, d = xs.shape
    K = slots.shape[0]
    buf = jnp.zeros((DPl, E * C, d), xs.dtype)
    for j in range(K):
        buf = buf.at[jnp.arange(DPl)[:, None], slots[j]].add(xs, mode="drop")
    return buf


def _combine_local(hflat, slots, weights, *, E, C):
    """Per-shard combine.  hflat: (DPl, E*C, d).  Returns (DPl, Tl, d)."""
    K = slots.shape[0]
    out = None
    for j in range(K):
        safe = jnp.minimum(slots[j], E * C - 1)[..., None]
        rows = jnp.take_along_axis(hflat, safe, axis=1, mode="clip")
        contrib = rows * weights[j][..., None]
        out = contrib if out is None else out + contrib
    return out


def _shardmapped(fn, args, **kw):
    """Run ``fn`` with the leading dp dim manual (shard_map) when a sharding
    context is installed; direct call otherwise (single-device tests)."""
    import functools
    from jax.sharding import PartitionSpec as P
    ctx = shard_ctx.current()
    if ctx is None:
        return fn(*args, **kw)
    dp = ctx.dp if len(ctx.dp) > 1 else ctx.dp[0]
    # arg 0 carries the dp dim leading (DP, ...); the rest are (K, DP, ...)
    in_specs = tuple(
        P(dp, *([None] * (a.ndim - 1))) if i == 0
        else P(None, dp, *([None] * (a.ndim - 2)))
        for i, a in enumerate(args))
    f = jax.shard_map(functools.partial(fn, **kw), mesh=ctx.mesh,
                  in_specs=in_specs, out_specs=P(dp, None, None),
                  axis_names=set(ctx.dp))
    return f(*args)
