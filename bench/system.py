"""The system under test, as a tenant reaches it: the program's daemon,
gateway and job specification, built from a configuration file's sizes.

This is the only module of the benchmark that imports the program.  What a
configuration file states and the program cannot be told (a width it fixes
in code) is checked here, so that the program runs what the file says."""
from __future__ import annotations

import os
import sys


def import_program(root: str):
    sys.path.insert(0, os.path.join(root, "src"))


def model_config(cfg):
    from repro.models.config import AttentionConfig, ModelConfig, XLSTMConfig
    if cfg["family"] == "xlstm":
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
    if cfg["family"] == "dense":
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
    raise ValueError(f"no model for family {cfg['family']!r}")


def train_job(cfg, mix, seed: int):
    from repro.core.runtime import JobSpec
    from repro.models.config import ShapeConfig
    from repro.train.optimizer import OptConfig
    o = cfg["optimizer"]
    assert o["moments"] == "float32"
    opt = OptConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                    weight_decay=o["weight_decay"], clip_norm=o["clip_norm"],
                    warmup_steps=o["warmup_steps"],
                    total_steps=o["total_steps"],
                    min_lr_frac=o["min_lr_frac"])
    shape = ShapeConfig("bench", "train", seq_len=mix["seq_len"],
                        global_batch=mix["global_batch"],
                        microbatch=mix["microbatch"])
    return JobSpec(model_config(cfg), shape, seed=seed, collect_metrics=True,
                   opt=opt)


def serve_job(cfg, seed: int):
    from repro.core.runtime import JobSpec
    from repro.models.config import ShapeConfig
    s = cfg["serve"]
    smax = cfg["max_position_embeddings"]
    return JobSpec(model_config(cfg),
                   ShapeConfig("bench", "serve", seq_len=smax,
                               global_batch=s["max_slots"]),
                   kind="serve", paged=True, page_size=s["page_size"],
                   max_slots=s["max_slots"], max_seq_len=smax, seed=seed)


def start_daemon(devices, ckpt_root: str):
    from repro.core.daemon import ClusterDaemon
    from repro.core.topology import host_topology
    topo, ordered = host_topology(list(devices))
    return ClusterDaemon(topo, devices=ordered, background=True,
                         ckpt_root=ckpt_root)


def start_gateway(daemon, user: str, token: str):
    from repro.gateway import GatewayServer, ProfileStore, UserProfile
    return GatewayServer(daemon, ProfileStore([UserProfile(user, token)]),
                         host="127.0.0.1", port=0).start()


def tracer():
    from repro.obs.trace import TRACER
    return TRACER
