"""Public kernel entry points used by the model stack.

Each op has up to three implementations:
  * ``jnp``    — chunked, O(S*chunk)-memory pure-jnp path.  This is what the
                 models lower through on the CPU and in multi-chip blocks.
  * ``pallas`` — the TPU kernel (``flash_attention.py`` / ``rmsnorm.py`` /
                 ``ssd_scan.py``), validated on CPU via interpret mode.
  * ``ref``    — naive oracle in ``ref.py`` (tests only).

``impl='auto'`` picks pallas on TPU backends and jnp elsewhere, with one
exception: inside a block whose mesh spans several chips the Pallas calls
would need manual partitioning (GSPMD cannot split a Mosaic kernel), so
such blocks lower every op through XLA and say so once with a warning.
"""
from __future__ import annotations

import functools
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding import ctx as shard_ctx

NEG_INF = -1.0e30


def _use_pallas(impl: str) -> bool:
    if impl == "pallas":
        return True
    if impl == "jnp":
        return False
    if jax.default_backend() != "tpu":
        return False
    ctx = shard_ctx.current()
    if ctx is not None and ctx.mesh.size > 1:
        warnings.warn(f"a {ctx.mesh.size}-chip block lowers its kernels "
                      f"through XLA: Pallas kernels run on 1-chip blocks")
        return False
    return True


# ===========================================================================
# Flash attention (training / prefill)
# ===========================================================================

def flash_attention(q, k, v, *, causal: bool = True, sliding_window: int = 0,
                    scale: Optional[float] = None, q_offset: int = 0,
                    q_chunk: int = 1024, kv_chunk: int = 1024,
                    impl: str = "auto"):
    """Memory-efficient attention.  Shapes as in ``ref.attention``.

    q: (B, Hq, Sq, D); k: (B, Hkv, Sk, D); v: (B, Hkv, Sk, Dv).
    """
    del q_chunk  # full-q tiles per kv chunk in the jnp path
    if _use_pallas(impl):
        return _flash_pallas(q, k, v, causal, sliding_window, scale,
                             q_offset, kv_chunk)
    return _flash_jnp(q, k, v, causal, sliding_window, scale, q_offset,
                      kv_chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_pallas(q, k, v, causal, sliding_window, scale, q_offset,
                  kv_chunk):
    """Pallas forward; the backward is the jnp flash backward, with the
    row log-sum-exp recomputed in XLA (a pallas_call has no derivative of
    its own)."""
    from repro.kernels import flash_attention as _fa
    return _fa.flash_attention_pallas(
        q, k, v, causal=causal, sliding_window=sliding_window, scale=scale,
        q_offset=q_offset, interpret=(jax.default_backend() != "tpu"))


def _flash_pallas_fwd(q, k, v, causal, window, scale, q_offset, kv_chunk):
    o = _flash_pallas(q, k, v, causal, window, scale, q_offset, kv_chunk)
    return o, (q, k, v, o)


def _flash_pallas_bwd(causal, window, scale, q_offset, kv_chunk, res, do):
    q, k, v, o = res
    _, lse = _flash_fwd_impl(q, k, v, causal, window, scale, q_offset,
                             kv_chunk)
    return _flash_bwd_rule(causal, window, scale, q_offset, kv_chunk,
                           (q, k, v, o, lse), do)


_flash_pallas.defvjp(_flash_pallas_fwd, _flash_pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_jnp(q, k, v, causal, sliding_window, scale, q_offset, kv_chunk):
    """Chunked flash attention with a flash *backward* (custom VJP): neither
    direction materializes the (Sq, Sk) score matrix.  The CPU stand-in for
    the Pallas kernels; the ``vmem_fused_flash`` scopes tell the roofline
    analyzer the score tiles are VMEM-resident on TPU."""
    o, _ = _flash_fwd_impl(q, k, v, causal, sliding_window, scale, q_offset,
                           kv_chunk)
    return o


def _mask_for(q_pos, k_pos, Sk, causal, window):
    mask = k_pos[None, :] < Sk                         # strip kv padding
    if causal:
        mask = mask & (q_pos[:, None] >= k_pos[None, :])
    if window > 0:
        mask = mask & ((q_pos[:, None] - k_pos[None, :]) < window)
    return mask


def _flash_fwd_impl(q, k, v, causal, window, scale, q_offset, kv_chunk):
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, Dv = v.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    kv_chunk = min(kv_chunk, Sk)
    Sk_p = -(-Sk // kv_chunk) * kv_chunk
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
    nk = Sk_p // kv_chunk
    k_blocks = jnp.moveaxis(kp.reshape(B, Hkv, nk, kv_chunk, D), 2, 0)
    v_blocks = jnp.moveaxis(vp.reshape(B, Hkv, nk, kv_chunk, Dv), 2, 0)
    qg = q.reshape(B, Hkv, G, Sq, D)
    q32 = qg.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(Sq)

    with jax.named_scope("vmem_fused_flash"):
        def kv_step(carry, inp):
            m, l, acc = carry
            ki, k_blk, v_blk = inp
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q32,
                           k_blk.astype(jnp.float32)) * scale
            mask = _mask_for(q_pos, k_pos, Sk, causal, window)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None]) * mask[None, None, None]
            corr = jnp.exp(m - m_new)
            l = l * corr + p.sum(axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p, v_blk.astype(jnp.float32))
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hkv, G, Sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, Sq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, Sq, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (jnp.arange(nk), k_blocks, v_blocks))
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o = (acc / l_safe[..., None]).reshape(B, Hq, Sq, Dv).astype(q.dtype)
        lse = m + jnp.log(l_safe)                     # (B, Hkv, G, Sq)
    return o, lse


def _flash_fwd_rule(q, k, v, causal, window, scale, q_offset, kv_chunk):
    o, lse = _flash_fwd_impl(q, k, v, causal, window, scale, q_offset,
                             kv_chunk)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, window, scale, q_offset, kv_chunk, res, do):
    """Flash backward: per kv chunk, recompute the normalized p tile from
    (q, k, lse) and accumulate dq/dk/dv — no stacked score residuals."""
    q, k, v, o, lse = res
    B, Hq, Sq, D = q.shape
    _, Hkv, Sk, Dv = v.shape
    G = Hq // Hkv
    scale_v = scale if scale is not None else 1.0 / np.sqrt(D)
    kv_c = min(kv_chunk, Sk)
    Sk_p = -(-Sk // kv_c) * kv_c
    nk = Sk_p // kv_c
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
    k_blocks = jnp.moveaxis(kp.reshape(B, Hkv, nk, kv_c, D), 2, 0)
    v_blocks = jnp.moveaxis(vp.reshape(B, Hkv, nk, kv_c, Dv), 2, 0)

    qg = q.reshape(B, Hkv, G, Sq, D).astype(jnp.float32)
    og = o.reshape(B, Hkv, G, Sq, Dv).astype(jnp.float32)
    dog = do.reshape(B, Hkv, G, Sq, Dv).astype(jnp.float32)
    delta = jnp.sum(og * dog, axis=-1)                    # (B,Hkv,G,Sq)
    q_pos = q_offset + jnp.arange(Sq)

    with jax.named_scope("vmem_fused_flash_bwd"):
        def kv_step(dq_acc, inp):
            ki, k_blk, v_blk = inp
            k_pos = ki * kv_c + jnp.arange(kv_c)
            kf = k_blk.astype(jnp.float32)
            vf = v_blk.astype(jnp.float32)
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, kf) * scale_v
            mask = _mask_for(q_pos, k_pos, Sk, causal, window)
            p = jnp.exp(s - lse[..., None]) * mask[None, None, None]
            dv_j = jnp.einsum("bhgqk,bhgqd->bhkd", p, dog)
            dp = jnp.einsum("bhgqd,bhkd->bhgqk", dog, vf)
            ds = p * (dp - delta[..., None]) * scale_v
            dq_acc = dq_acc + jnp.einsum("bhgqk,bhkd->bhgqd", ds, kf)
            dk_j = jnp.einsum("bhgqk,bhgqd->bhkd", ds, qg)
            return dq_acc, (dk_j, dv_j)

        dq0 = jnp.zeros((B, Hkv, G, Sq, D), jnp.float32)
        dq, (dk_blocks, dv_blocks) = jax.lax.scan(
            kv_step, dq0, (jnp.arange(nk), k_blocks, v_blocks))
        dk = jnp.moveaxis(dk_blocks, 0, 2).reshape(B, Hkv, Sk_p, D)[:, :, :Sk]
        dv = jnp.moveaxis(dv_blocks, 0, 2).reshape(B, Hkv, Sk_p, Dv)[:, :, :Sk]
    return (dq.reshape(B, Hq, Sq, D).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


_flash_jnp.defvjp(_flash_fwd_rule, _flash_bwd_rule)


# ===========================================================================
# Decode attention (single new token vs. a cache)
# ===========================================================================

def decode_attention(q, k_cache, v_cache, cache_len, *, scale=None,
                     sliding_window: int = 0):
    """q: (B, Hq, 1, D); caches: (B, Hkv, Smax, D|Dv); cache_len: () int32.

    Attends over the first ``cache_len`` cache entries (the new token's K/V is
    assumed already written at position cache_len-1).
    """
    B, Hq, _, D = q.shape
    _, Hkv, Smax, Dv = v_cache.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k_cache.astype(jnp.float32)) * scale
    pos = jnp.arange(Smax)
    mask = pos < cache_len
    if sliding_window > 0:
        mask &= pos >= (cache_len - sliding_window)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v_cache.astype(jnp.float32))
    return o.reshape(B, Hq, 1, Dv).astype(q.dtype)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, layer, *,
                    scale=None, impl: str = "auto"):
    """Decode attention against one layer of a stacked paged KV pool
    (continuous batching).

    q: (B, Hq, 1, D); pools: (L, n_pages, page, Hkv, D|Dv);
    page_table: (B, maxp) int32; seq_lens: (B,) int32 — valid entries per
    slot (the new token's K/V already written at position seq_lens-1);
    layer: () int32, the pool to attend in.  Returns (B, Hq, 1, Dv).

    Sequence position ``p`` of slot ``b`` lives at row ``p % page`` of page
    ``page_table[b, p // page]`` of pool ``layer``, so the gathered view
    reproduces the dense cache layout and the masked softmax below is
    ``decode_attention`` with a per-slot length vector instead of one
    scalar ``cache_len``.
    """
    B, Hq, _, D = q.shape
    _, _, page, Hkv, Dv = v_pages.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(D)
    if _use_pallas(impl):
        from repro.kernels import paged_attention as _pa
        o = _pa.paged_attention_pallas(
            q[:, :, 0], k_pages, v_pages, page_table, seq_lens, layer,
            scale=scale, interpret=(jax.default_backend() != "tpu"))
        return o[:, :, None]
    G = Hq // Hkv
    S = page_table.shape[1] * page
    k = k_pages[layer, page_table].reshape(B, S, Hkv, D).swapaxes(1, 2)
    v = v_pages[layer, page_table].reshape(B, S, Hkv, Dv).swapaxes(1, 2)
    qf = q.astype(jnp.float32).reshape(B, Hkv, G, D)
    s = jnp.einsum("bhgd,bhkd->bhgk", qf, k.astype(jnp.float32)) * scale
    pos = jnp.arange(S)
    mask = pos[None, :] < seq_lens[:, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, Hq, 1, Dv).astype(q.dtype)


# ===========================================================================
# RMSNorm
# ===========================================================================

def rmsnorm(x, scale, *, eps: float = 1e-6, impl: str = "auto"):
    if _use_pallas(impl):
        return _rmsnorm_pallas(x, scale, eps)
    return _rmsnorm_jnp(x, scale, eps)


def _rmsnorm_jnp(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_pallas(x, scale, eps):
    """Pallas forward; the backward recomputes the norm in XLA (a
    pallas_call has no derivative of its own, and the train step
    differentiates through every norm)."""
    from repro.kernels import rmsnorm as _rn
    return _rn.rmsnorm_pallas(x, scale, eps=eps,
                              interpret=(jax.default_backend() != "tpu"))


def _rmsnorm_pallas_fwd(x, scale, eps):
    return _rmsnorm_pallas(x, scale, eps), (x, scale)


def _rmsnorm_pallas_bwd(eps, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda x_, s_: _rmsnorm_jnp(x_, s_, eps), x, scale)
    return vjp(g)


_rmsnorm_pallas.defvjp(_rmsnorm_pallas_fwd, _rmsnorm_pallas_bwd)


# ===========================================================================
# Fused AdamW optimizer update (single HBM pass per leaf)
# ===========================================================================

def fused_adamw(p, g, m, v, *, lr, scale, bc1, bc2, b1, b2, eps,
                weight_decay, apply_wd: Optional[bool] = None,
                impl: str = "auto"):
    """One leaf's AdamW update; m/v are fp32 arrays or ``quantized_state``
    {"q", "s"} dicts and return in the same format.

    The pallas path (``fused_adamw.py``) does the whole update — dequantize,
    moment update, bias-corrected delta, decoupled weight decay, param cast,
    requantize — in one read/write per array instead of the ~6 HBM passes
    the composed ``quantized_state`` + ``_adam_leaf`` ops lower to.  The jnp
    path replays the exact reference op sequence (bit-identical to
    ``optimizer._adam_leaf``).  ``apply_wd`` defaults to ``p.ndim >= 2``
    (decay matrices only), matching the reference.
    """
    if apply_wd is None:
        apply_wd = p.ndim >= 2
    if _use_pallas(impl):
        from repro.kernels import fused_adamw as _fo
        return _fo.fused_adamw_update(
            p, g, m, v, lr=lr, scale=scale, bc1=bc1, bc2=bc2, b1=b1, b2=b2,
            eps=eps, weight_decay=weight_decay, apply_wd=apply_wd,
            interpret=(jax.default_backend() != "tpu"))
    return _fused_adamw_jnp(p, g, m, v, lr=lr, scale=scale, bc1=bc1, bc2=bc2,
                            b1=b1, b2=b2, eps=eps, weight_decay=weight_decay,
                            apply_wd=apply_wd)


def _fused_adamw_jnp(p, g, m, v, *, lr, scale, bc1, bc2, b1, b2, eps,
                     weight_decay, apply_wd):
    from repro.train import quantized_state as qs
    quantized = isinstance(m, dict)
    g = g.astype(jnp.float32) * scale
    m_f = qs.dequantize(m) if quantized else m
    v_f = qs.dequantize(v) if quantized else v
    m_f = b1 * m_f + (1 - b1) * g
    v_f = b2 * v_f + (1 - b2) * g * g
    delta = (m_f / bc1) / (jnp.sqrt(v_f / bc2) + eps)
    if apply_wd:
        delta = delta + weight_decay * p.astype(jnp.float32)
    new_p = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
    if quantized:
        return new_p, qs.quantize(m_f), qs.quantize(v_f)
    return new_p, m_f, v_f


# ===========================================================================
# Mamba2 SSD chunked scan
# ===========================================================================

def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 256, h0=None,
             impl: str = "auto"):
    """Chunked state-space-dual scan.  Shapes as in ``ref.ssd_scan``.

    Returns (y, h_final).  O(S*chunk) memory, O(S*chunk + S*N*P) flops.
    """
    if _use_pallas(impl):
        from repro.kernels import ssd_scan as _ssd
        return _ssd.ssd_scan_pallas(x, dt, A, B, C, D, chunk=chunk, h0=h0,
                                    interpret=(jax.default_backend() != "tpu"))
    return _ssd_jnp(x, dt, A, B, C, D, chunk=chunk, h0=h0)


def _ssd_jnp(x, dt, A, B, C, D, *, chunk, h0):
    """``vmem_fused_ssd``: stand-in for the Pallas SSD kernel — the (Q x Q)
    intra-chunk decay matrices and the recurrent state stay in VMEM on TPU;
    the analyzer charges boundary traffic only."""
    with jax.named_scope("vmem_fused_ssd"):
        return _ssd_jnp_body(x, dt, A, B, C, D, chunk=chunk, h0=h0)


def _ssd_jnp_body(x, dt, A, B, C, D, *, chunk, h0):
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    Sp = -(-S // Q) * Q
    pad = Sp - S

    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    dtf = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    Bf = jnp.pad(B.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    Cf = jnp.pad(C.astype(jnp.float32), ((0, 0), (0, pad), (0, 0)))
    nc = Sp // Q

    # (nc, Bt, Q, ...)
    xc = jnp.moveaxis(xf.reshape(Bt, nc, Q, H, P), 1, 0)
    dtc = jnp.moveaxis(dtf.reshape(Bt, nc, Q, H), 1, 0)
    Bc = jnp.moveaxis(Bf.reshape(Bt, nc, Q, N), 1, 0)
    Cc = jnp.moveaxis(Cf.reshape(Bt, nc, Q, N), 1, 0)
    Af = A.astype(jnp.float32)

    h_init = (jnp.zeros((Bt, H, P, N), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))

    def chunk_step(h, inp):
        x_c, dt_c, B_c, C_c = inp           # (Bt,Q,H,P) (Bt,Q,H) (Bt,Q,N) (Bt,Q,N)
        dA = dt_c * Af[None, None]          # (Bt,Q,H)
        a = jnp.cumsum(dA, axis=1)          # within-chunk cumulative log decay
        # inter-chunk: y_inter[t] = C_t . (exp(a_t) * h)
        y_inter = jnp.einsum("bqn,bqh,bhpn->bqhp", C_c, jnp.exp(a), h)
        # intra-chunk: L[t,j] = exp(a_t - a_j) for t >= j
        seg = a[:, :, None, :] - a[:, None, :, :]          # (Bt,Q,Q,H)
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        L = jnp.where(tri[None, :, :, None], jnp.exp(seg), 0.0)
        cb = jnp.einsum("bqn,bjn->bqj", C_c, B_c)          # (Bt,Q,Q)
        y_intra = jnp.einsum("bqj,bqjh,bjh,bjhp->bqhp", cb, L, dt_c, x_c)
        # carry: h' = exp(a_Q) h + sum_j exp(a_Q - a_j) dt_j B_j x_j^T
        decay_end = jnp.exp(a[:, -1])                       # (Bt,H)
        w = jnp.exp(a[:, -1:, :] - a) * dt_c               # (Bt,Q,H)
        h_new = (h * decay_end[..., None, None]
                 + jnp.einsum("bqh,bqn,bqhp->bhpn", w, B_c, x_c))
        return h_new, y_inter + y_intra

    h_fin, yc = jax.lax.scan(chunk_step, h_init, (xc, dtc, Bc, Cc))
    y = jnp.moveaxis(yc, 0, 1).reshape(Bt, Sp, H, P)[:, :S]
    y = y + x.astype(jnp.float32) * D[None, None, :, None]
    return y.astype(x.dtype), h_fin


def ssd_decode_step(x, dt, A, B, C, D, h):
    """Single-token Mamba2 update.  x:(Bt,H,P) dt:(Bt,H) B,C:(Bt,N) h:(Bt,H,P,N)."""
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(dtf * A[None])
    h = h * decay[..., None, None] + jnp.einsum(
        "bh,bn,bhp->bhpn", dtf, B.astype(jnp.float32), xf)
    y = jnp.einsum("bn,bhpn->bhp", C.astype(jnp.float32), h) + xf * D[None, :, None]
    return y.astype(x.dtype), h


# ===========================================================================
# mLSTM chunked scan (xLSTM matrix memory)
# ===========================================================================

def mlstm_scan(q, k, v, i_gate, f_gate, *, chunk: int = 256, carry=None,
               impl: str = "auto"):
    """Chunkwise-parallel stabilized mLSTM.  Shapes as in ``ref.mlstm_scan``.

    Returns (h, (C, n, m)).  Matches the sequential reference exactly
    (same running-max stabilizer).  The ``vmem_fused_mlstm`` scope marks the
    chunk scan as VMEM-resident for the roofline analyzer (the (Dk x Dv)
    matrix state fits VMEM for every assigned config).
    """
    del impl  # single jnp implementation; pallas variant covers ssd_scan
    with jax.named_scope("vmem_fused_mlstm"):
        return _mlstm_scan_body(q, k, v, i_gate, f_gate, chunk=chunk,
                                carry=carry)


def _mlstm_scan_body(q, k, v, i_gate, f_gate, *, chunk, carry):
    B, H, S, Dk = q.shape
    Dv = v.shape[-1]
    scale = 1.0 / np.sqrt(Dk)
    Q = min(chunk, S)
    Sp = -(-S // Q) * Q
    pad = Sp - S

    def pad_s(t):
        return jnp.pad(t.astype(jnp.float32),
                       ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 3))

    qf, kf, vf = pad_s(q), pad_s(k), pad_s(v)
    # padded forget gates -> log f = 0 would corrupt the running max; use
    # i=-inf (no write) and f=+inf (log f ~ 0 fine since no writes occur).
    igf = jnp.pad(i_gate.astype(jnp.float32), ((0, 0), (0, 0), (0, pad)),
                  constant_values=NEG_INF)
    fgf = jnp.pad(f_gate.astype(jnp.float32), ((0, 0), (0, 0), (0, pad)),
                  constant_values=80.0)
    nc = Sp // Q

    def to_chunks(t):
        return jnp.moveaxis(t.reshape(B, H, nc, Q, *t.shape[3:]), 2, 0)

    qc, kc, vc = to_chunks(qf), to_chunks(kf), to_chunks(vf)
    ic, fc = to_chunks(igf), to_chunks(fgf)

    if carry is None:
        C0 = jnp.zeros((B, H, Dk, Dv), jnp.float32)
        n0 = jnp.zeros((B, H, Dk), jnp.float32)
        m0 = jnp.full((B, H), -jnp.inf, jnp.float32)
    else:
        C0, n0, m0 = (c.astype(jnp.float32) for c in carry)

    def chunk_step(state, inp):
        C, n, m = state
        q_c, k_c, v_c, i_c, f_c = inp       # (B,H,Q,*)
        logf = jax.nn.log_sigmoid(f_c)      # (B,H,Q)
        G = jnp.cumsum(logf, axis=-1)       # local cumulative log forget
        # D_local[t,j] = G_t - G_j + i_j  for j <= t
        d_loc = G[..., :, None] - G[..., None, :] + i_c[..., None, :]
        tri = jnp.tril(jnp.ones((Q, Q), bool))
        d_loc = jnp.where(tri, d_loc, -jnp.inf)
        # running max m_t = max(m_prev + G_t, max_j<=t d_loc[t,j]) — row t of
        # d_loc already contains every j <= t (with its decay), so the row
        # max IS the full local running max; a cummax over rows would mix in
        # stale (undecayed) values and break the carry's exp(-m) scaling.
        m_t = jnp.maximum(m[..., None] + G, jnp.max(d_loc, axis=-1))
        # intra-chunk scores
        s = jnp.einsum("bhqd,bhjd->bhqj", q_c, k_c) * scale
        w = jnp.where(tri, jnp.exp(d_loc - m_t[..., None]), 0.0)
        num_i = jnp.einsum("bhqj,bhqj,bhjv->bhqv", s, w, v_c)
        den_i = jnp.einsum("bhqj,bhqj->bhq", s, w)
        # inter-chunk: decay from carry
        inter_w = jnp.exp(m[..., None] + G - m_t)            # (B,H,Q)
        num_x = jnp.einsum("bhkv,bhqk->bhqv", C, q_c) * scale * inter_w[..., None]
        den_x = jnp.einsum("bhk,bhqk->bhq", n, q_c) * scale * inter_w
        den = jnp.maximum(jnp.abs(den_i + den_x), jnp.exp(-m_t))
        h = (num_i + num_x) / den[..., None]
        # carry update at chunk end with m_end
        m_end = m_t[..., -1]
        cw = jnp.exp(G[..., -1:] - G + i_c - m_end[..., None])   # (B,H,Q)
        C_new = (C * jnp.exp(m + G[..., -1] - m_end)[..., None, None]
                 + jnp.einsum("bhq,bhqk,bhqv->bhkv", cw, k_c, v_c))
        n_new = (n * jnp.exp(m + G[..., -1] - m_end)[..., None]
                 + jnp.einsum("bhq,bhqk->bhk", cw, k_c))
        return (C_new, n_new, m_end), h

    (Cf_, nf_, mf_), hc = jax.lax.scan(chunk_step, (C0, n0, m0),
                                       (qc, kc, vc, ic, fc))
    h = jnp.moveaxis(hc, 0, 2).reshape(B, H, Sp, Dv)[:, :, :S]
    return h.astype(q.dtype), (Cf_, nf_, mf_)


def mlstm_decode_step(q, k, v, i_gate, f_gate, carry):
    """Single-token mLSTM update.  q,k:(B,H,Dk) v:(B,H,Dv) gates:(B,H)."""
    C, n, m = carry
    scale = 1.0 / np.sqrt(q.shape[-1])
    logf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))
    m_new = jnp.maximum(logf + m, i_gate.astype(jnp.float32))
    fg = jnp.exp(logf + m - m_new)
    ig = jnp.exp(i_gate.astype(jnp.float32) - m_new)
    kf, vf, qf = (t.astype(jnp.float32) for t in (k, v, q))
    C = C * fg[..., None, None] + ig[..., None, None] * (kf[..., :, None] * vf[..., None, :])
    n = n * fg[..., None] + ig[..., None] * kf
    num = jnp.einsum("bhkv,bhk->bhv", C, qf) * scale
    den = jnp.maximum(jnp.abs(jnp.einsum("bhk,bhk->bh", n, qf)) * scale,
                      jnp.exp(-m_new))
    return (num / den[..., None]).astype(q.dtype), (C, n, m_new)
