"""Pallas paged-attention kernel (single-token decode, GQA).

Continuous-batching serve blocks keep every live session's K/V in one
block-granular *page pool* instead of a per-sequence ``smax`` allocation:

    k_pages / v_pages : (L, n_pages, page_size, Hkv, D | Dv)  every layer's
                                                               pool, stacked
    page_table        : (B, pages_per_seq) int32  slot -> page ids (all layers)
    seq_lens          : (B,) int32                valid tokens/slot
    layer             : () int32                  the layer to attend in

Sequence position ``p`` of slot ``b`` lives at row ``p % page_size`` of page
``page_table[b, p // page_size]`` in each layer's pool, so the gathered rows
``[0, seq_lens[b])`` reproduce the dense cache layout and decode attention is
the same masked softmax the dense path computes, fetched page by page out
of the pool.

Kernel structure: grid ``(B, pages_per_seq)`` with the page sweep
minor-most.  ``page_table``, ``seq_lens`` and ``layer`` ride scalar
prefetch (``pltpu.PrefetchScalarGridSpec``) so the K/V BlockSpec index maps
chase the page table, in the layer's slice of the stacked pool, when
scheduling block DMAs: the gather happens in the pipeline, never as a
materialized (B, S, ...) copy, and no layer's pool is sliced out of the
stack.  The decode loop keeps the stacked pool in its carry and writes each
layer's new rows in place, so the pool is never copied.  Pages past a slot's
last live page map to that last page again, so the pipeline skips their
DMA, and ``pl.when`` skips their compute.

Each page folds into an online softmax (running max ``m``, normalizer
``l`` and fp32 accumulator per query group, in VMEM scratch that persists
across the page sweep, as ``flash_attention`` does across kv blocks).  The
query arrives group-major, ``(B, G, Hkv, D)``, so every array in the kernel
keeps the page's ``(rows, Hkv, D)`` layout: scores are a lane reduction
over ``D`` and the weighted sum a reduction over the page rows, with no
batched dot and no transpose for Mosaic to refuse.  VMEM holds one page of
K and V per step, whatever the sequence length.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30   # TPU-safe -inf stand-in (same convention as flash_attention)


def _paged_attention_kernel(pt_ref, sl_ref, layer_ref, q_ref, k_ref, v_ref,
                            o_ref, m_scr, l_scr, acc_scr, *,
                            pages_per_seq: int, page: int, groups: int,
                            scale: float):
    b = pl.program_id(0)
    j = pl.program_id(1)
    seq_len = sl_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * page < seq_len)
    def _body():
        k = k_ref[0, 0].astype(jnp.float32)                # (page, Hkv, D)
        v = v_ref[0, 0].astype(jnp.float32)                # (page, Hkv, Dv)
        pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (page, k.shape[1], 1), 0)
        live = pos < seq_len                               # (page, Hkv, 1)
        for g in range(groups):
            q = q_ref[0, g].astype(jnp.float32)            # (Hkv, D)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
            s = jnp.where(live, s, NEG_INF)                # (page, Hkv, 1)
            m_prev = m_scr[g]                              # (Hkv, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None]) * live.astype(jnp.float32)
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=0)
            acc_scr[g] = acc_scr[g] * corr + jnp.sum(p * v, axis=0)
            m_scr[g] = m_new

    @pl.when(j == pages_per_seq - 1)
    def _finalize():
        for g in range(groups):
            l = l_scr[g]
            l = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, g] = (acc_scr[g] / l).astype(o_ref.dtype)


def paged_attention_pallas(q, k_pages, v_pages, page_table, seq_lens, layer,
                           *, scale: float | None = None,
                           interpret: bool = False):
    """q: (B, Hq, D); pools: (L, P, page, Hkv, D|Dv); page_table: (B, maxp)
    int32; seq_lens: (B,) int32; layer: () int32.  Returns (B, Hq, Dv).

    Attends each slot's single query over its ``seq_lens[b]`` gathered cache
    entries of pool ``layer`` (the new token's K/V already written at
    position ``seq_lens[b] - 1``).  A caller holding one layer's pool passes
    ``pool[None]`` and layer 0.  Pages beyond a slot's allocation may point
    anywhere valid (the reserved trash page): their rows are masked out.
    """
    B, Hq, D = q.shape
    _, _, page, Hkv, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    G = Hq // Hkv
    maxp = page_table.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    # head h*G + g of the query serves kv head h: group-major (B, G, Hkv, D)
    qg = q.reshape(B, Hkv, G, D).swapaxes(1, 2)

    def kv_index(b, j, pt_ref, sl_ref, layer_ref):
        # pages past the slot's last live page re-name that page: an
        # unchanged block index means the pipeline starts no new DMA
        last = jnp.maximum(sl_ref[b] - 1, 0) // page
        return (layer_ref[0], pt_ref[b, jnp.minimum(j, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, G, Hkv, D), lambda b, j, *_: (b, 0, 0, 0)),
            pl.BlockSpec((1, 1, page, Hkv, D), kv_index),
            pl.BlockSpec((1, 1, page, Hkv, Dv), kv_index),
        ],
        out_specs=pl.BlockSpec((1, G, Hkv, Dv), lambda b, j, *_: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
            pltpu.VMEM((G, Hkv, 1), jnp.float32),
            pltpu.VMEM((G, Hkv, Dv), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_attention_kernel, pages_per_seq=maxp,
                               page=page, groups=G, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, Hkv, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(page_table, seq_lens, jnp.asarray(layer, jnp.int32).reshape(1), qg,
      k_pages, v_pages)
    return out.swapaxes(1, 2).reshape(B, Hq, Dv)
