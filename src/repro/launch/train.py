"""End-to-end training driver (single block, real execution) — runs
through the ClusterDaemon service layer: the job is registered, admitted
and activated as a block (the full paper lifecycle), stepped through the
event-driven dispatcher, and monitored via the event bus, exactly like a
tenant of the public cluster.  Nothing here constructs a controller
directly.

  PYTHONPATH=src python -m repro.launch.train --arch xlstm_350m --steps 200 \
      --seq-len 256 --global-batch 8 --smoke
"""
from __future__ import annotations

import argparse
import time

import jax

import repro.configs as configs
from repro.core.daemon import ClusterDaemon
from repro.core.runtime import JobSpec
from repro.core.topology import host_topology
from repro.models import model as model_lib
from repro.models.config import ShapeConfig
from repro.train import compile_cache
from repro.train import optimizer as opt_lib


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced same-family config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--autostep", action="store_true",
                    help="daemon-side stepping: the cluster's autostep "
                         "engine drives the block to --steps (checkpoints "
                         "included); no client step loop")
    ap.add_argument("--pace", type=float, default=None,
                    help="with --autostep: cap the engine at this many "
                         "steps/s")
    args = ap.parse_args(argv)
    compile_cache.use_persistent_cache()

    cfg = (configs.get_smoke(args.arch) if args.smoke
           else configs.get(args.arch))
    shape = ShapeConfig("cli", "train", seq_len=args.seq_len,
                        global_batch=args.global_batch,
                        microbatch=args.microbatch)
    opt_cfg = opt_lib.OptConfig(lr=args.lr,
                                warmup_steps=max(args.steps // 20, 1),
                                total_steps=args.steps)

    # one block spanning every available device, granted by the daemon
    # (--autostep needs the background pump: the engine steps from there)
    topo, devices = host_topology(jax.devices())
    n_dev = topo.n_chips
    daemon = ClusterDaemon(topo, devices=devices,
                           ckpt_root=args.ckpt_dir or "artifacts/train_ckpt",
                           background=args.autostep)
    job = JobSpec(cfg, shape, opt=opt_cfg, seed=args.seed,
                  collect_metrics=True,
                  # stable namespace so --resume finds earlier runs
                  ckpt_namespace=cfg.name if args.ckpt_dir else None,
                  # periodic checkpoints under autostep come from the
                  # engine (client-driven mode saves between chunks below)
                  ckpt_every=(args.ckpt_every if args.ckpt_dir else 0))
    app_id, grant = daemon.submit("cli", f"train {cfg.name}", n_dev,
                                  job=job)
    assert grant is not None, "single-tenant pod must admit immediately"
    rt = daemon.runtime(app_id)
    n_params = model_lib.count_params(rt.state["params"])
    print(f"# arch={cfg.name} params={n_params/1e6:.2f}M devices={n_dev} "
          f"block={grant.block_id} "
          f"tokens/step={shape.global_batch * shape.seq_len}")

    start_step = 0
    if args.ckpt_dir and args.resume:
        at = daemon.restore(app_id)
        if at is not None:
            start_step = rt.step_count
            print(f"# resumed from step {start_step}")

    losses = []

    def on_step(ev):
        """Event-bus monitoring: each completed step carries its metrics
        (collect_metrics=True) through the async dispatch window."""
        p = ev.payload
        m = p.get("metrics") or {}
        step = daemon.monitor.steps_done(ev.block_id) + start_step - 1
        if "loss" in m:
            losses.append(m["loss"])
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {m.get('loss', float('nan')):8.4f} "
                  f"gnorm {m.get('grad_norm', float('nan')):8.3f} "
                  f"lr {m.get('lr', 0.0):.2e}", flush=True)

    daemon.bus.subscribe(on_step, kinds={"step"})

    t_start = time.time()
    if args.autostep:
        # daemon-side execution: arm the engine and watch — zero client
        # step calls; progress, metrics and checkpoints all flow from the
        # pump thread through the event bus
        from repro.core.block import BlockState
        daemon.autostep_enable(app_id, until_steps=args.steps,
                               max_rate_hz=args.pace)
        blk = daemon.registry.get(app_id)
        # the block's lease is the run's deadline (the pump expires it)
        while blk.state not in (BlockState.DONE, BlockState.FAILED,
                                BlockState.EXPIRED):
            if time.time() > blk.grant.expires_at + 60.0:
                print(f"# block {app_id} still {blk.state.value} past its "
                      f"lease", flush=True)
                daemon.stop()
                return 1
            time.sleep(0.1)
        if blk.state is not BlockState.DONE:
            print(f"# block {app_id} ended {blk.state.value}: "
                  f"{blk.failure_reason}", flush=True)
            daemon.stop()
            return 1
        if args.ckpt_dir and args.ckpt_every:
            daemon.save(app_id, async_=True)   # final-step checkpoint
    else:
        done = start_step
        while done < args.steps:
            chunk = min(args.ckpt_every or args.steps, args.steps - done)
            daemon.run_steps({app_id: chunk})
            done += chunk
            if args.ckpt_dir and args.ckpt_every:
                daemon.save(app_id, async_=True)
    wall = time.time() - t_start

    rt.ckpt.wait()                # an async save may still be landing
    res = daemon.download(app_id)
    tok_s = ((args.steps - start_step) * shape.global_batch *
             shape.seq_len / wall)
    loss_span = (f"loss {losses[0]:.4f} -> {losses[-1]:.4f}"
                 if losses else "loss n/a")
    print(f"# done: {wall:.1f}s, {tok_s:.0f} tok/s, {loss_span}, "
          f"checkpoints={res['checkpoints']}")
    daemon.expire(app_id)
    daemon.stop()          # no-op in deterministic (non --autostep) mode
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
