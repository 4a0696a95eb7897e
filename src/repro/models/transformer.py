"""Stack assembly for every architecture family.

Every family is expressed as a repeated *group* of sublayers so the whole
stack lowers to one ``jax.lax.scan`` over stacked group params (small HLO,
remat-friendly):

  dense / encoder : group = [attn + mlp]
  moe (every=1)   : group = [attn + moe]                     (deepseek-v2, MLA)
  moe (every=2)   : group = [attn + mlp, attn + moe]         (llama4-maverick)
  xlstm           : group = [mLSTM x (k-1), sLSTM x 1]
  hybrid          : group = [mamba2 x m, shared-attn + mlp]  (zamba2; attn
                    params are weight-shared across groups -> passed as
                    non-scanned closure constants)

``group_fwd`` handles train (no cache), prefill (cache written) and decode
(S==1, cache read+written) uniformly.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models import ssm
from repro.models.config import ModelConfig
from repro.models.layers import (_he, apply_norm, attention_fwd,
                                 attention_init, mla_fwd, mla_init, mlp_fwd,
                                 mlp_init, norm_init, paged_attention_fwd)
from repro.models.moe import moe_fwd, moe_init
from repro.sharding import ctx as shard_ctx


# ---------------------------------------------------------------------------
# group structure
# ---------------------------------------------------------------------------

def group_size(cfg: ModelConfig) -> int:
    if cfg.family == "xlstm":
        return cfg.xlstm.slstm_every
    if cfg.family == "hybrid":
        return cfg.hybrid.mamba_per_group + 1
    if cfg.family == "moe" and cfg.d_ff > 0:
        return 2  # alternating dense / moe
    return 1


def n_groups(cfg: ModelConfig) -> int:
    g = group_size(cfg)
    assert cfg.n_layers % g == 0, (cfg.name, cfg.n_layers, g)
    return cfg.n_layers // g


# ---------------------------------------------------------------------------
# per-group init
# ---------------------------------------------------------------------------

def _attn_init(key, cfg: ModelConfig, dtype):
    if cfg.attention.is_mla:
        return mla_init(key, cfg.d_model, cfg.attention, dtype)
    return attention_init(key, cfg.d_model, cfg.attention, dtype)


def _dense_sublayer_init(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": norm_init(cfg.d_model, cfg.norm, dtype),
        "attn": _attn_init(k1, cfg, dtype),
        "ln2": norm_init(cfg.d_model, cfg.norm, dtype),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, cfg.mlp_gated, dtype),
    }


def _moe_sublayer_init(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "ln1": norm_init(cfg.d_model, cfg.norm, dtype),
        "attn": _attn_init(k1, cfg, dtype),
        "ln2": norm_init(cfg.d_model, cfg.norm, dtype),
        "moe": moe_init(k2, cfg.d_model, cfg.moe, dtype),
    }


def group_init(key, cfg: ModelConfig, dtype):
    fam = cfg.family
    if fam in ("dense", "encoder", "vlm"):
        return _dense_sublayer_init(key, cfg, dtype)
    if fam == "moe":
        if cfg.d_ff > 0:
            k1, k2 = jax.random.split(key)
            return {"dense": _dense_sublayer_init(k1, cfg, dtype),
                    "moe": _moe_sublayer_init(k2, cfg, dtype)}
        return _moe_sublayer_init(key, cfg, dtype)
    if fam == "xlstm":
        n_m = cfg.xlstm.slstm_every - 1
        keys = jax.random.split(key, n_m + 1)
        m_params = jax.vmap(
            lambda k: {"ln": norm_init(cfg.d_model, cfg.norm, dtype),
                       "blk": ssm.mlstm_init(k, cfg.d_model, cfg.xlstm, dtype)}
        )(keys[:n_m])
        s_params = {"ln": norm_init(cfg.d_model, cfg.norm, dtype),
                    "blk": ssm.slstm_init(keys[-1], cfg.d_model, cfg.xlstm, dtype)}
        return {"mlstm": m_params, "slstm": s_params}
    if fam == "hybrid":
        n_m = cfg.hybrid.mamba_per_group
        keys = jax.random.split(key, n_m)
        m_params = jax.vmap(
            lambda k: {"ln": norm_init(cfg.d_model, cfg.norm, dtype),
                       "blk": ssm.mamba2_init(k, cfg.d_model, cfg.ssm, dtype)}
        )(keys)
        return {"mamba": m_params}
    raise ValueError(fam)


def shared_extra_init(key, cfg: ModelConfig, dtype):
    """Weight-shared sublayers applied once per group (zamba2 attention)."""
    if cfg.family == "hybrid":
        return _dense_sublayer_init(key, cfg, dtype)
    return None


# ---------------------------------------------------------------------------
# per-group forward
# ---------------------------------------------------------------------------

def _dense_sublayer_fwd(p, x, cfg, *, positions, cache, cache_len, causal=None,
                        page_table=None, seq_lens=None, layer=None):
    h = apply_norm(p["ln1"], x, cfg.norm)
    # `is not None`: an all-zeros page table is a valid (trash-only) table
    if page_table is not None:
        a, new_cache = paged_attention_fwd(p["attn"], h, cfg.attention,
                                           pages=cache,
                                           page_table=page_table,
                                           seq_lens=seq_lens, layer=layer)
    elif cfg.attention.is_mla:
        a, new_cache = mla_fwd(p["attn"], h, cfg.attention,
                               positions=positions, cache=cache,
                               cache_len=cache_len)
    else:
        a, new_cache = attention_fwd(p["attn"], h, cfg.attention,
                                     positions=positions, cache=cache,
                                     cache_len=cache_len, causal=causal)
    x = x + a
    h = apply_norm(p["ln2"], x, cfg.norm)
    x = x + mlp_fwd(p["mlp"], h, cfg.act, cfg.mlp_gated)
    return x, new_cache


def _moe_sublayer_fwd(p, x, cfg, *, positions, cache, cache_len,
                      page_table=None, seq_lens=None, layer=None):
    h = apply_norm(p["ln1"], x, cfg.norm)
    if page_table is not None:
        a, new_cache = paged_attention_fwd(p["attn"], h, cfg.attention,
                                           pages=cache,
                                           page_table=page_table,
                                           seq_lens=seq_lens, layer=layer)
    elif cfg.attention.is_mla:
        a, new_cache = mla_fwd(p["attn"], h, cfg.attention,
                               positions=positions, cache=cache,
                               cache_len=cache_len)
    else:
        a, new_cache = attention_fwd(p["attn"], h, cfg.attention,
                                     positions=positions, cache=cache,
                                     cache_len=cache_len)
    x = x + a
    h = apply_norm(p["ln2"], x, cfg.norm)
    m, aux = moe_fwd(p["moe"], h, cfg.moe, cfg.act)
    return x + m, aux, new_cache


def group_fwd(gp, x, cfg: ModelConfig, *, positions, cache, cache_len, extra,
              page_table=None, seq_lens=None, layer=None):
    """Returns (x, aux, new_cache).  ``cache`` is this group's cache (or
    None); on the paged path it is every group's stacked pool, of which
    the group attends in and writes pool ``layer``."""
    fam = cfg.family
    aux = jnp.zeros((), jnp.float32)
    if fam in ("dense", "vlm"):
        x, nc = _dense_sublayer_fwd(gp, x, cfg, positions=positions,
                                    cache=cache, cache_len=cache_len,
                                    page_table=page_table, seq_lens=seq_lens,
                                    layer=layer)
        return x, aux, nc
    if fam == "encoder":
        x, nc = _dense_sublayer_fwd(gp, x, cfg, positions=positions,
                                    cache=None, cache_len=None, causal=False)
        return x, aux, None
    if fam == "moe":
        if cfg.d_ff > 0:
            c_d = None if cache is None else cache["dense"]
            c_m = None if cache is None else cache["moe"]
            x, nc_d = _dense_sublayer_fwd(gp["dense"], x, cfg,
                                          positions=positions, cache=c_d,
                                          cache_len=cache_len,
                                          page_table=page_table,
                                          seq_lens=seq_lens, layer=layer)
            x, aux, nc_m = _moe_sublayer_fwd(gp["moe"], x, cfg,
                                             positions=positions, cache=c_m,
                                             cache_len=cache_len,
                                             page_table=page_table,
                                             seq_lens=seq_lens, layer=layer)
            nc = None if cache is None else {"dense": nc_d, "moe": nc_m}
            return x, aux, nc
        x, aux, nc = _moe_sublayer_fwd(gp, x, cfg, positions=positions,
                                       cache=cache, cache_len=cache_len,
                                       page_table=page_table,
                                       seq_lens=seq_lens, layer=layer)
        return x, aux, nc
    if fam == "xlstm":
        def m_step(x, inp):
            lp, st = inp
            h = apply_norm(lp["ln"], x, cfg.norm)
            y, new_st = ssm.mlstm_fwd(lp["blk"], h, cfg.xlstm, cfg.d_model,
                                      state=st)
            return x + y, new_st
        m_states = None if cache is None else cache["mlstm"]
        # the scope holds the sublayer scan itself, so its loop (and the
        # backward's) is charged to the mLSTM blocks too
        with jax.named_scope("mlstm"):
            x, new_m = _scan_sublayers(m_step, x, gp["mlstm"], m_states,
                                       cfg.xlstm.slstm_every - 1)
        s_state = None if cache is None else cache["slstm"]
        with jax.named_scope("slstm"):
            h = apply_norm(gp["slstm"]["ln"], x, cfg.norm)
            y, new_s = ssm.slstm_fwd(gp["slstm"]["blk"], h, cfg.xlstm,
                                     cfg.d_model, state=s_state)
            x = x + y
        nc = None if cache is None else {"mlstm": new_m, "slstm": new_s}
        return x, aux, nc
    if fam == "hybrid":
        def m_step(x, inp):
            lp, st = inp
            h = apply_norm(lp["ln"], x, cfg.norm)
            y, new_st = ssm.mamba2_fwd(lp["blk"], h, cfg.ssm, cfg.d_model,
                                       state=st)
            return x + y, new_st
        m_states = None if cache is None else cache["mamba"]
        x, new_m = _scan_sublayers(m_step, x, gp["mamba"], m_states,
                                   cfg.hybrid.mamba_per_group)
        # weight-shared attention block (params from `extra`, cache per group)
        a_cache = None if cache is None else cache["attn"]
        x, new_a = _dense_sublayer_fwd(extra, x, cfg, positions=positions,
                                       cache=a_cache, cache_len=cache_len)
        nc = None if cache is None else {"mamba": new_m, "attn": new_a}
        return x, aux, nc
    raise ValueError(fam)


def _scan_sublayers(step, x, stacked_params, stacked_states, n: int):
    """Scan ``step`` over n stacked sublayers (params + optional states)."""
    if stacked_states is None:
        def body(c, lp):
            y, st = step(c, (lp, None))
            return y, st
        return jax.lax.scan(body, x, stacked_params)
    def body(c, inp):
        lp, st = inp
        y, new_st = step(c, (lp, st))
        return y, new_st
    return jax.lax.scan(body, x, (stacked_params, stacked_states))


# ---------------------------------------------------------------------------
# cache init (actual arrays; decode/prefill state)
# ---------------------------------------------------------------------------

def _attn_cache_init(cfg: ModelConfig, batch: int, smax: int):
    a = cfg.attention
    dt = jnp.dtype(cfg.param_dtype)
    if a.is_mla:
        return {"c_kv": jnp.zeros((batch, smax, a.kv_lora_rank), dt),
                "k_rope": jnp.zeros((batch, smax, a.qk_rope_head_dim), dt)}
    return {"k": jnp.zeros((batch, smax, a.n_kv_heads, a.head_dim), dt),
            "v": jnp.zeros((batch, smax, a.n_kv_heads, a.v_dim), dt)}


def _zeros_from_spec(spec):
    return jax.tree.map(lambda s: jnp.zeros(s[0], s[1]), spec,
                        is_leaf=lambda s: isinstance(s, tuple)
                        and len(s) == 2 and isinstance(s[0], tuple))


def group_cache_init(cfg: ModelConfig, batch: int, smax: int):
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return _attn_cache_init(cfg, batch, smax)
    if fam == "encoder":
        return None
    if fam == "moe":
        c = _attn_cache_init(cfg, batch, smax)
        if cfg.d_ff > 0:
            return {"dense": _attn_cache_init(cfg, batch, smax), "moe": c}
        return c
    if fam == "xlstm":
        n_m = cfg.xlstm.slstm_every - 1
        one_m = {
            "conv": jnp.zeros((batch, 3, int(cfg.xlstm.proj_factor * cfg.d_model)),
                              jnp.bfloat16),
            "mlstm": _mlstm_zero_carry(cfg, batch),
        }
        m = jax.tree.map(lambda t: jnp.broadcast_to(t, (n_m,) + t.shape), one_m)
        H = cfg.xlstm.n_heads
        Dh = cfg.d_model // H
        s = {"slstm": (jnp.zeros((batch, H, Dh), jnp.float32),
                       jnp.zeros((batch, H, Dh), jnp.float32),
                       jnp.ones((batch, H, Dh), jnp.float32),
                       jnp.zeros((batch, H, Dh), jnp.float32))}
        return {"mlstm": m, "slstm": s}
    if fam == "hybrid":
        n_m = cfg.hybrid.mamba_per_group
        spec = ssm.mamba2_state_spec(cfg.ssm, cfg.d_model, batch)
        one = _zeros_from_spec(spec)
        m = jax.tree.map(lambda t: jnp.broadcast_to(t, (n_m,) + t.shape), one)
        return {"mamba": m, "attn": _attn_cache_init(cfg, batch, smax)}
    raise ValueError(fam)


def _mlstm_zero_carry(cfg: ModelConfig, batch: int):
    inner, Dk, Dv, H = ssm._mlstm_dims(cfg.d_model, cfg.xlstm)
    return (jnp.zeros((batch, H, Dk, Dv), jnp.float32),
            jnp.zeros((batch, H, Dk), jnp.float32),
            jnp.full((batch, H), -jnp.inf, jnp.float32))


def init_cache(cfg: ModelConfig, batch: int, smax: int):
    """Stacked (n_groups, ...) cache pytree."""
    one = group_cache_init(cfg, batch, smax)
    if one is None:
        return None
    ng = n_groups(cfg)
    return jax.tree.map(lambda t: jnp.broadcast_to(t[None], (ng,) + t.shape)
                        .astype(t.dtype), one)


# ---------------------------------------------------------------------------
# paged cache (continuous-batching serve)
# ---------------------------------------------------------------------------

def check_paged_support(cfg: ModelConfig) -> None:
    """Paged decode covers the plain-GQA attention families; recurrent
    states (xlstm/hybrid) and MLA's compressed cache page differently and
    stay on the dense path."""
    if cfg.family not in ("dense", "vlm", "moe") or cfg.attention is None:
        raise ValueError(
            f"paged decode unsupported for family {cfg.family!r}")
    if cfg.attention.is_mla:
        raise ValueError("paged decode does not support MLA caches")
    if cfg.attention.sliding_window > 0:
        raise ValueError("paged decode does not support sliding windows")


def _paged_group_cache_init(cfg: ModelConfig, n_pages: int, page_size: int):
    a = cfg.attention
    dt = jnp.dtype(cfg.param_dtype)

    def one():
        return {"k": jnp.zeros((n_pages, page_size, a.n_kv_heads,
                                a.head_dim), dt),
                "v": jnp.zeros((n_pages, page_size, a.n_kv_heads,
                                a.v_dim), dt)}

    if cfg.family == "moe" and cfg.d_ff > 0:
        return {"dense": one(), "moe": one()}
    return one()


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int):
    """Stacked (n_groups, ...) page-pool pytree shared by all live slots.
    Page 0 is reserved as the trash page (never allocated to a session):
    inactive slots' table rows point at it so their scatter writes and
    gathered garbage stay masked out."""
    check_paged_support(cfg)
    one = _paged_group_cache_init(cfg, n_pages, page_size)
    ng = n_groups(cfg)
    return jax.tree.map(lambda t: jnp.broadcast_to(t[None], (ng,) + t.shape)
                        .astype(t.dtype), one)


# ---------------------------------------------------------------------------
# full-stack params + forward
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_layers, k_extra, k_head, k_mask = jax.random.split(key, 5)
    ng = n_groups(cfg)
    layer_keys = jax.random.split(k_layers, ng)
    layers = jax.vmap(lambda k: group_init(k, cfg, dtype))(layer_keys)
    params: Dict[str, Any] = {"layers": layers}
    if cfg.frontend == "frame":
        params["frame_proj"] = _he(k_emb, (cfg.frontend_dim, cfg.d_model), dtype)
        params["mask_embed"] = (jax.random.normal(k_mask, (cfg.d_model,),
                                                  jnp.float32) * 0.02).astype(dtype)
    else:
        params["embed"] = (jax.random.normal(
            k_emb, (cfg.vocab_size, cfg.d_model), jnp.float32) * 0.02
        ).astype(dtype)
    if cfg.frontend == "patch":
        params["patch_proj"] = _he(k_extra, (cfg.frontend_dim, cfg.d_model), dtype)
    extra = shared_extra_init(k_extra, cfg, dtype)
    if extra is not None:
        params["extra"] = extra
    params["final_norm"] = norm_init(cfg.d_model, cfg.norm, dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = _he(k_head, (cfg.d_model, cfg.vocab_size), dtype)
    return params


def embed_inputs(params, cfg: ModelConfig, batch: Dict[str, Any]):
    """Build the (B, S, d) input activations from the batch dict."""
    if cfg.frontend == "frame":
        x = batch["frames"].astype(jnp.bfloat16) @ params["frame_proj"]
        if "mask" in batch:
            x = jnp.where(batch["mask"][..., None],
                          params["mask_embed"][None, None], x)
        return x
    with jax.named_scope("lm_head"):
        tok = params["embed"][batch["tokens"]]
    if cfg.frontend == "patch" and "patches" in batch:
        patches = batch["patches"].astype(jnp.bfloat16) @ params["patch_proj"]
        tok = jnp.concatenate([patches, tok], axis=1)
    return shard_ctx.constrain_tokens_3d(tok)


def forward(params, cfg: ModelConfig, x, *, positions, cache=None,
            cache_len=None, page_table=None, seq_lens=None):
    """Run the stack on embedded inputs x: (B, S, d).

    With ``page_table``/``seq_lens`` set, ``cache`` is the stacked paged
    pool from ``init_paged_cache`` and decode runs the paged-attention path
    (the table and lengths are shared across groups).  Returns (logits
    (B, S, V), aux_loss, new_cache).
    """
    extra = params.get("extra")
    if page_table is not None:
        x, aux, new_cache = _paged_layers(params, cfg, x, positions, cache,
                                          page_table, seq_lens, extra)
    else:
        x, aux, new_cache = _layers(params, cfg, x, positions, cache,
                                    cache_len, extra)
    with jax.named_scope("lm_head"):
        x = apply_norm(params["final_norm"], x, cfg.norm)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = x @ head
        if cfg.logits_softcap > 0:
            logits = (jnp.tanh(logits / cfg.logits_softcap)
                      * cfg.logits_softcap)
        logits = shard_ctx.constrain_logits(logits)
    return logits, aux, new_cache


def _layers(params, cfg: ModelConfig, x, positions, cache, cache_len, extra):
    """The layer scan of training (``cache`` None), prefill and dense
    decode: each group's cache slice enters as ``xs`` and its new cache
    leaves stacked as ``ys``."""

    def body(carry, inp):
        x, aux = carry
        if cache is None:
            gp, gc = inp, None
        else:
            gp, gc = inp
        x, a, nc = group_fwd(gp, x, cfg, positions=positions, cache=gc,
                             cache_len=cache_len, extra=extra)
        return (x, aux + a), nc

    if cfg.remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat == "dots" else None)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    xs = params["layers"] if cache is None else (params["layers"], cache)
    (x, aux), new_cache = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)), xs)
    return x, aux, (None if cache is None else new_cache)


def _paged_layers(params, cfg: ModelConfig, x, positions, pool, page_table,
                  seq_lens, extra):
    """The layer scan of paged decode.  The stacked pool rides in the
    carry, one buffer for the whole loop: each group writes its slots' new
    rows into its own layer of it in place and the kernel reads its pages
    by layer index, so no layer's pool is sliced out and no stacked copy
    is collected as ``ys``."""

    def body(carry, inp):
        x, aux, pool = carry
        gp, layer = inp
        x, a, pool = group_fwd(gp, x, cfg, positions=positions, cache=pool,
                               cache_len=None, extra=extra,
                               page_table=page_table, seq_lens=seq_lens,
                               layer=layer)
        return (x, aux + a, pool), None

    (x, aux, pool), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), pool),
        (params["layers"], jnp.arange(n_groups(cfg), dtype=jnp.int32)))
    return x, aux, pool
