"""xLSTM[m:s]: stacked mLSTM and sLSTM blocks, trained."""
from __future__ import annotations

from bench import flops as F


def model_config(cfg):
    from repro.models.config import ModelConfig, XLSTMConfig
    assert cfg["conv_width"] == 4, "the program's mLSTM conv is width 4"
    assert abs(cfg["slstm_ff_factor"] - 4 / 3) < 1e-9, \
        "the program's sLSTM up-projection is 4/3"
    return ModelConfig(
        name=cfg["name"], family="xlstm",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        vocab_size=cfg["vocab_size"], d_ff=0,
        xlstm=XLSTMConfig(n_heads=cfg["num_heads"],
                          proj_factor=cfg["proj_factor"],
                          qk_factor=cfg["qk_factor"],
                          slstm_every=cfg["slstm_every"],
                          chunk=cfg["chunk_size"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        param_dtype=cfg["param_dtype"])


def train_step_flops(cfg, B: int, S: int) -> float:
    return F.xlstm_train_step_flops(cfg, B, S)
