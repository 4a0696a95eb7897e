"""Operations and bytes of each kernel and of each whole step, computed from
shapes.  A FLOP is one multiply or one add (a multiply-accumulate is 2).
Counts are of what the algorithm needs: recomputation that a program does
to save memory is not counted."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def scale(self, k: float) -> "Work":
        return Work(self.flops * k, self.bytes * k)

    def least_time(self, peak_flops: float, peak_bw: float):
        """(seconds, bound): the larger of the two lower bounds and which
        one it is ('compute' or 'memory')."""
        tc, tm = self.flops / peak_flops, self.bytes / peak_bw
        return (tc, "compute") if tc >= tm else (tm, "memory")


# ------------------------------------------------------------------ xLSTM

def xlstm_dims(cfg):
    d, H = cfg["hidden_size"], cfg["num_heads"]
    inner = int(cfg["proj_factor"] * d)
    qk = int(cfg["qk_factor"] * inner)
    return d, H, inner, qk // H, inner // H


def mlstm_scan_fwd(B: int, H: int, S: int, Dk: int, Dv: int, Q: int,
                   elem_bytes: int = 2) -> Work:
    """One forward call of the chunkwise mLSTM scan over (B, H, S).

    Per chunk of Q and head: scores q.k (2 Q^2 Dk), the weighted sum of
    values (2 Q^2 Dv), the normaliser row sums (2 Q^2), the carried state
    read by the queries (2 Q Dk Dv + 2 Q Dk) and its update
    (2 Q Dk Dv + 2 Q Dk).  Bytes: q, k, v and both gates read once, h
    written once."""
    nc = -(-S // Q)
    per_chunk = (2 * Q * Q * Dk + 2 * Q * Q * Dv + 2 * Q * Q
                 + 4 * Q * Dk * Dv + 4 * Q * Dk)
    flops = B * H * nc * per_chunk
    byt = B * H * S * (2 * Dk + 2 * Dv + 2) * elem_bytes
    return Work(float(flops), float(byt))


def xlstm_fwd_flops_per_token(cfg) -> float:
    """Matrix products of one token's forward pass, without the mLSTM
    sequence mixing (see ``mlstm_scan_fwd``)."""
    d, H, inner, Dk, Dv = xlstm_dims(cfg)
    W = cfg["conv_width"]
    L = cfg["num_hidden_layers"]
    n_s = L // cfg["slstm_every"]
    n_m = L - n_s
    Dh = d // H
    ff = int(d * cfg["slstm_ff_factor"])
    m_layer = 2 * (d * 2 * inner + inner * H * Dk * 2 + inner * H * Dv
                   + inner * 2 * H + inner * d) + 2 * W * inner
    s_layer = 2 * (d * 4 * d + H * Dh * 4 * Dh + 3 * d * ff)
    head = 2 * d * cfg["vocab_size"]
    return float(n_m * m_layer + n_s * s_layer + head)


def xlstm_train_step_flops(cfg, B: int, S: int) -> float:
    """Model FLOPs of one training step: forward and backward (3x the
    forward) of every matrix product and of the mLSTM chunk terms."""
    d, H, inner, Dk, Dv = xlstm_dims(cfg)
    L = cfg["num_hidden_layers"]
    n_m = L - L // cfg["slstm_every"]
    fwd = (B * S * xlstm_fwd_flops_per_token(cfg)
           + n_m * mlstm_scan_fwd(B, H, S, Dk, Dv, cfg["chunk_size"]).flops)
    return 3.0 * fwd


# ---------------------------------------------------------------- llama

def llama_dims(cfg):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, H, cfg["num_key_value_heads"], d // H, cfg["intermediate_size"]


def llama_decode_token_flops(cfg, ctx: int) -> float:
    """One decoded token whose cache holds ``ctx`` earlier tokens: every
    weight once (2 FLOPs a weight) and attention over ctx + 1 positions
    (q.k and p.v, 4 H D a position) in each layer."""
    d, H, Hkv, D, ff = llama_dims(cfg)
    L = cfg["num_hidden_layers"]
    per_layer = 2 * (2 * d * H * D + 2 * d * Hkv * D + 3 * d * ff)
    return float(L * (per_layer + 4 * (ctx + 1) * H * D)
                 + 2 * d * cfg["vocab_size"])


def paged_attention_call(cfg, ctxs, elem_bytes: int = 2) -> Work:
    """One paged-attention call (one layer, one token per slot) over slots
    whose caches hold ``ctxs`` earlier tokens each: q.k and p.v over
    ctx + 1 positions, the keys and values of those positions read once,
    q read and the output written once per slot."""
    d, H, Hkv, D, _ = llama_dims(cfg)
    flops = sum(4 * (c + 1) * H * D for c in ctxs)
    byt = sum(2 * (c + 1) * Hkv * D * elem_bytes + 2 * H * D * elem_bytes
              for c in ctxs)
    return Work(float(flops), float(byt))
