"""Model facade: the public API the runtime layers (train/serve) consume.

  init_params(cfg, key)                  -> params pytree
  abstract_params(cfg)                   -> ShapeDtypeStruct pytree (no alloc)
  loss_fn(params, cfg, batch)            -> (loss, metrics)
  prefill(params, cfg, batch, cache)     -> (logits_last, filled_cache)
  decode_step(params, cfg, token, cache, cache_len) -> (logits, new_cache)
  init_cache(cfg, batch, smax)           -> cache pytree
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer
from repro.models.config import ModelConfig
from repro.models.transformer import (embed_inputs, forward,  # re-export
                                      init_cache, init_paged_cache)

init_params = transformer.init_params
check_paged_support = transformer.check_paged_support


def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        lambda k: transformer.init_params(cfg, k),
        jax.ShapeDtypeStruct((2,), jnp.uint32))


def count_params(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


def count_active_params(cfg: ModelConfig) -> int:
    """Params touched per token: full count minus inactive routed experts."""
    total = count_params(abstract_params(cfg))
    if cfg.moe is None:
        return total
    m = cfg.moe
    per_expert = 3 * cfg.d_model * m.d_ff_expert
    n_moe_layers = transformer.n_groups(cfg)   # one moe sublayer per group
    inactive = n_moe_layers * (m.n_experts - m.top_k) * per_expert
    return total - inactive


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

@jax.named_scope("lm_head")
def _xent(logits, labels, mask):
    """Cross-entropy in fp32 with a validity mask.  logits: (B,S,V)."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = (lse - gold) * mask
    denom = jnp.maximum(mask.sum(), 1.0)
    return nll.sum() / denom


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, Any]
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x = embed_inputs(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S)
    logits, aux, _ = forward(params, cfg, x, positions=positions)

    if cfg.frontend == "frame":
        # masked-prediction (HuBERT-style): loss only on masked frames
        labels = batch["labels"]
        mask = batch["mask"].astype(jnp.float32)
        loss = _xent(logits, labels, mask)
    elif cfg.frontend == "patch":
        # next-token on the text segment only (patches occupy the prefix)
        n_p = batch["patches"].shape[1]
        labels = batch["labels"]                       # (B, S_text)
        text_logits = logits[:, n_p:]
        loss = _next_token_loss(text_logits, labels)
    else:
        loss = _next_token_loss(logits, batch["labels"])
    total = loss + aux
    return total, {"loss": loss, "aux_loss": aux}


def _next_token_loss(logits, labels):
    """Standard causal LM loss: logits[t] predicts labels[t]."""
    mask = jnp.ones(labels.shape, jnp.float32)
    return _xent(logits, labels, mask)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, batch: Dict[str, Any], cache):
    """Run the prompt through the stack, filling ``cache``.

    Returns (logits_last (B, V), cache)."""
    x = embed_inputs(params, cfg, batch)
    S = x.shape[1]
    positions = jnp.arange(S)
    logits, _, new_cache = forward(params, cfg, x, positions=positions,
                                   cache=cache, cache_len=jnp.int32(0))
    return logits[:, -1], new_cache


def decode_step(params, cfg: ModelConfig, token, cache, cache_len):
    """One autoregressive step.  token: (B, 1) int32; cache_len: scalar int32.

    Returns (logits (B, V), new_cache)."""
    x = embed_inputs(params, cfg, {"tokens": token})
    positions = cache_len + jnp.arange(1)
    logits, _, new_cache = forward(params, cfg, x, positions=positions,
                                   cache=cache, cache_len=cache_len)
    return logits[:, -1], new_cache


# ---------------------------------------------------------------------------
# paged serving (continuous batching)
# ---------------------------------------------------------------------------

def decode_step_paged(params, cfg: ModelConfig, token, cache, page_table,
                      seq_lens):
    """One decode step for every slot of a continuous batch.

    token: (B, 1) int32 — each slot's last token (garbage for idle slots);
    cache: stacked paged pool from ``init_paged_cache``;
    page_table: (B, maxp) int32; seq_lens: (B,) int32 per-slot cache fill
    (idle slots: 0 with a trash-page table row).
    Returns (logits (B, V), new_cache)."""
    x = embed_inputs(params, cfg, {"tokens": token})
    positions = seq_lens[:, None]
    logits, _, new_cache = forward(params, cfg, x, positions=positions,
                                   cache=cache, cache_len=None,
                                   page_table=page_table, seq_lens=seq_lens)
    return logits[:, -1], new_cache


def write_prefill_to_pages(pool, dense_cache, page_ids, page_size: int):
    """Scatter a freshly prefilled dense cache (batch=1, smax a multiple of
    ``page_size``) into the paged pool at the allocated ``page_ids``.

    Leaf shapes: dense (ng, 1, smax, Hkv, D) -> pool (ng, n_pages, page,
    Hkv, D).  The dense prefill wrote positions [0, plen); trailing rows of
    the last page carry the dense cache's zero padding, overwritten in
    place on later decode steps.  Bit-preserving: page row ``p`` receives
    exactly dense row ``p``."""
    ids = jnp.asarray(page_ids, jnp.int32)
    npg = ids.shape[0]

    def put(p, d):
        ng, _, smax = d.shape[:3]
        assert smax == npg * page_size, (smax, npg, page_size)
        src = d[:, 0].reshape((ng, npg, page_size) + d.shape[3:])
        return p.at[:, ids].set(src.astype(p.dtype))

    return jax.tree.map(put, pool, dense_cache)
