"""FLOP and byte counts against hand counts at small shapes."""
import pytest

from bench import flops as F


def test_mlstm_scan_fwd_hand_count():
    # B=H=1, S=4, Q=2 -> 2 chunks; per chunk: q.k 2*2*2*2=16, weighted
    # values 2*2*2*3=24, row sums 2*2*2=8, state read 2*2*2*3 + 2*2*2=32,
    # state update 32 -> 112 a chunk
    w = F.mlstm_scan_fwd(1, 1, 4, 2, 3, 2, elem_bytes=2)
    assert w.flops == 224
    # q, k (2 each), v (3), two gates, h (3) per position, 4 positions
    assert w.bytes == 4 * (2 * 2 + 2 * 3 + 2) * 2


def _llama(L=1):
    return {"hidden_size": 4, "num_attention_heads": 2,
            "num_key_value_heads": 2, "intermediate_size": 6,
            "num_hidden_layers": L, "vocab_size": 10}


def test_decode_token_hand_count():
    # q,k,v,o 4 x (4x4) weights, 3 x (4x6) MLP: 2 FLOPs a weight = 272;
    # attention over 4 positions: 4 * 4 * (2 heads * 2) = 64; head 2*4*10
    assert F.llama_decode_token_flops(_llama(), ctx=3) == 272 + 64 + 80
    assert F.llama_decode_token_flops(_llama(L=2), ctx=3) == 2 * 336 + 80


def test_paged_attention_call_hand_count():
    w = F.paged_attention_call(_llama(), [0, 2], elem_bytes=2)
    # positions 1 and 3; 4 FLOPs a position per head dim unit: 4*1*4 +
    # 4*3*4
    assert w.flops == 64
    # k and v of each position (2 heads x 2 dims x 2 bytes), q and o
    assert w.bytes == (2 * 1 * 4 * 2 + 2 * 4 * 2) + (2 * 3 * 4 * 2 + 2 * 4 * 2)


def test_least_time_names_its_bound():
    assert F.Work(100.0, 1.0).least_time(10.0, 10.0) == (10.0, "compute")
    assert F.Work(1.0, 100.0).least_time(10.0, 10.0) == (10.0, "memory")


def test_xlstm_step_counts_every_part():
    cfg = {"hidden_size": 8, "num_heads": 2, "proj_factor": 2.0,
           "qk_factor": 0.5, "num_hidden_layers": 2, "slstm_every": 2,
           "slstm_ff_factor": 4 / 3, "conv_width": 4, "vocab_size": 16,
           "chunk_size": 4}
    # d=8, inner=16, Dk=4, Dv=8, H=2, Dh=4, ff=10
    m_layer = 2 * (8 * 32 + 16 * 8 * 2 + 16 * 16 + 16 * 4 + 16 * 8) + 2 * 4 * 16
    s_layer = 2 * (8 * 32 + 2 * 4 * 16 + 3 * 8 * 10)
    per_tok = m_layer + s_layer + 2 * 8 * 16
    assert F.xlstm_fwd_flops_per_token(cfg) == per_tok
    scan = F.mlstm_scan_fwd(3, 2, 8, 4, 8, 4).flops
    assert F.xlstm_train_step_flops(cfg, 3, 8) == pytest.approx(
        3 * (3 * 8 * per_tok + scan))
