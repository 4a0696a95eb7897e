"""Serving driver: batched prefill + autoregressive decode — run as a
serve-kind block through the ClusterDaemon service layer (register ->
admit -> activate -> prefill -> decode steps -> download), so the CLI
exercises the same lifecycle, dispatcher and monitoring as any other
tenant of the public cluster.

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek_7b --smoke \
      --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as configs
from repro.core.daemon import ClusterDaemon
from repro.core.runtime import JobSpec
from repro.core.topology import host_topology
from repro.data import pipeline
from repro.models.config import ShapeConfig
from repro.train import compile_cache


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--sample", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.use_persistent_cache()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    if cfg.is_encoder:
        raise SystemExit("encoder-only arch has no decode path")
    B, P, G = args.batch, args.prompt_len, args.gen

    topo, devices = host_topology(jax.devices())
    n_dev = topo.n_chips
    daemon = ClusterDaemon(topo, devices=devices,
                           ckpt_root="artifacts/serve_ckpt")
    # cache sized for prompt + generation; the block's decode step and
    # (lazy) prefill both compile on its granted sub-mesh
    job = JobSpec(cfg, ShapeConfig("cli", "serve", seq_len=P + G,
                                   global_batch=B),
                  kind="serve", seed=args.seed, decode_sample=args.sample)
    app_id, grant = daemon.submit("cli", f"serve {cfg.name}", n_dev,
                                  job=job)
    assert grant is not None, "single-tenant pod must admit immediately"
    rt = daemon.runtime(app_id)

    prompt_shape = ShapeConfig("cli", "prefill", seq_len=P, global_batch=B)
    batch = {k: jnp.asarray(v) for k, v in pipeline.synthetic_batch(
        cfg, prompt_shape, step=0, seed=args.seed).items()
        if k != "labels"}

    t0 = time.time()
    rt.prefill(batch)
    jax.block_until_ready(rt.token)
    t_prefill = time.time() - t0

    out_tokens = [np.asarray(rt.token)]
    t0 = time.time()
    for _ in range(G - 1):
        # one dispatch round per generated token so every token is
        # collected (decode is a serial chain — no parallelism is lost)
        daemon.run_steps({app_id: 1})
        out_tokens.append(np.asarray(rt.token))
    t_decode = time.time() - t0

    gen = np.concatenate(out_tokens, axis=1)
    res = daemon.download(app_id)
    print(f"# arch={cfg.name} batch={B} prompt={P} gen={G} "
          f"block={grant.block_id}")
    print(f"# prefill: {t_prefill*1e3:.1f} ms "
          f"({B*P/t_prefill:.0f} tok/s)")
    print(f"# decode:  {t_decode*1e3:.1f} ms "
          f"({B*(G-1)/max(t_decode,1e-9):.0f} tok/s) "
          f"steps={res['steps']}")
    print("# first generations:", gen[:2, :10].tolist())
    daemon.expire(app_id)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
