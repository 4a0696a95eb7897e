"""The system under test, as a tenant reaches it: the program's daemon,
gateway and job specification, built from a configuration file's sizes.

The program is built here and in the family modules this module loads
(``bench/families/``); elsewhere a run reads only its layer-scope names
(``bench/scopes.py``), and ``bench/described_compile.py`` compiles it for
a described chip.  What a configuration file states and the
program cannot be told (a width it fixes in code) is checked in its family
module, so that the program runs what the file says."""
from __future__ import annotations

import os
import sys

from bench import families


def import_program(root: str):
    sys.path.insert(0, os.path.join(root, "src"))


def model_config(cfg):
    """The program's model configuration, built by the module of the
    configuration's ``family`` (``bench/families/``)."""
    return families.of(cfg).model_config(cfg)


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
