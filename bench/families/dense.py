"""Dense llama-architecture decoders (RMSNorm, rotary attention, SwiGLU),
served from a paged KV cache."""
from __future__ import annotations

from bench import flops as F


def model_config(cfg):
    from repro.models.config import AttentionConfig, ModelConfig
    assert cfg["hidden_act"] == "silu" and cfg["rms_norm_eps"] == 1e-6
    H = cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], d_ff=cfg["intermediate_size"],
        attention=AttentionConfig(
            n_heads=H, n_kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg["hidden_size"] // H,
            rope_theta=cfg["rope_theta"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["param_dtype"])


def decode_token_flops(cfg, ctx: int) -> float:
    return F.llama_decode_token_flops(cfg, ctx)


def paged_attention_calls(cfg, ctxs) -> F.Work:
    """The paged-attention kernel's work in one decode round: one call in
    every layer over slots whose caches hold ``ctxs`` earlier tokens."""
    return F.paged_attention_call(cfg, ctxs).scale(cfg["num_hidden_layers"])
