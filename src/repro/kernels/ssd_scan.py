"""Pallas TPU Mamba2 SSD chunked scan.

Grid = (B, H, n_chunks), chunk dim minor-most: the (state x head_dim) fp32
recurrent state lives in VMEM scratch across the sequential chunk sweep, so
inter-chunk state passing never round-trips HBM (the jnp fallback carries it
through a lax.scan, which XLA materializes per step).  Intra-chunk work is
the (Q x Q) decay-weighted quadratic form on the MXU.

Layout: heads move out of the two minor dims before the call (x and y as
(B, H, S, P), dt as a (Q, 1) column and a (1, Q) row per chunk, B as
(B, N, S) next to C as (B, S, N)), so every block's two minor dims are a
whole (chunk, feature) tile.  Every in-kernel product is then a plain or
rhs-transposed matmul, and the within-chunk cumulative decay is a masked
reduction of the (Q x Q) triangle, in both orientations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, d_ref, x_ref, dtc_ref, dtr_ref, bt_ref, c_ref, y_ref,
                h_scr, *, chunk: int):
    h_idx = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    A = a_ref[h_idx]                                # SMEM scalars
    D = d_ref[h_idx]
    x = x_ref[...].astype(jnp.float32)              # (Q, P)
    dt_col = dtc_ref[...].astype(jnp.float32)       # (Q, 1)
    dt_row = dtr_ref[...].astype(jnp.float32)       # (1, Q)
    Bt = bt_ref[...].astype(jnp.float32)            # (N, Q)
    Cm = c_ref[...].astype(jnp.float32)             # (Q, N)

    # within-chunk cumulative log decay a_t = sum_{j<=t} dt_j A, as a
    # column (row sums of the lower triangle) and as a row (column sums of
    # the upper one)
    row_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = row_i >= col_i
    a_col = jnp.sum(jnp.where(tri, dt_row * A, 0.0), axis=1,
                    keepdims=True)                  # (Q, 1)
    a_row = jnp.sum(jnp.where(row_i <= col_i, dt_col * A, 0.0), axis=0,
                    keepdims=True)                  # (1, Q)
    a_last = jnp.sum(dt_col * A, axis=0, keepdims=True)   # (1, 1)
    h_prev = h_scr[...]                             # (N, P)

    # inter-chunk: y_inter[t] = exp(a_t) * (C_t . h_prev)
    y_inter = jnp.exp(a_col) * jnp.dot(Cm, h_prev,
                                       preferred_element_type=jnp.float32)

    # intra-chunk: L[t,j] = exp(a_t - a_j) for t >= j
    L = jnp.where(tri, jnp.exp(a_col - a_row), 0.0)
    cb = jax.lax.dot_general(Cm, Bt, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q, Q)
    w = cb * L * dt_row
    y_intra = jnp.dot(w, x, preferred_element_type=jnp.float32)    # (Q, P)

    y_ref[...] = (y_inter + y_intra + x * D).astype(y_ref.dtype)

    # carry: h' = exp(a_Q) h + sum_j exp(a_Q - a_j) dt_j B_j x_j^T
    wj = jnp.exp(a_last - a_col) * dt_col           # (Q, 1)
    h_scr[...] = (h_prev * jnp.exp(a_last)
                  + jnp.dot(Bt, x * wj, preferred_element_type=jnp.float32))


def ssd_scan_pallas(x, dt, A, B, C, D, *, chunk: int = 256, h0=None,
                    interpret: bool = False):
    """Shapes as in ``ref.ssd_scan``: x (Bt,S,H,P), dt (Bt,S,H), A (H,),
    B/C (Bt,S,N), D (H,).  Returns (y, h_final) — h_final recomputed via the
    jnp reference tail when needed (prefill); train only consumes y."""
    assert h0 is None, "pallas path covers the from-zeros (train) case"
    Bt, S, H, P = x.shape
    N = B.shape[-1]
    Q = min(chunk, S)
    Sp = -(-S // Q) * Q
    pad = Sp - S
    # zero-padded tail: dt = 0 is an identity decay with no state write
    xh = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    dth = jnp.pad(dt, ((0, 0), (0, pad), (0, 0))).transpose(0, 2, 1)
    Cp = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    BT = jnp.pad(B, ((0, 0), (0, pad), (0, 0))).transpose(0, 2, 1)
    nc = Sp // Q

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    kernel = functools.partial(_ssd_kernel, chunk=Q)
    y = pl.pallas_call(
        kernel,
        grid=(Bt, H, nc),
        in_specs=[
            smem, smem,
            pl.BlockSpec((None, None, Q, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, Q, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, 1, Q), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((None, N, Q), lambda b, h, c: (b, 0, c)),
            pl.BlockSpec((None, Q, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, Q, P),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((Bt, H, Sp, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="ssd_scan",
    )(A.astype(jnp.float32), D.astype(jnp.float32), xh, dth[..., None],
      dth[:, :, None, :], BT, Cp)
    y = y.transpose(0, 2, 1, 3)[:, :S]
    # final state (for prefill-with-cache): cheap jnp recompute of the tail
    from repro.kernels import ops as _ops
    _, h_fin = _ops._ssd_jnp(x, dt, A, B, C, D, chunk=chunk, h0=None)
    return y, h_fin
