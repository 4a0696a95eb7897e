#!/usr/bin/env python3
"""Bring-up check: drive the main path once on TPU chips, through the
entry points a tenant uses (a ``ClusterDaemon`` over ``jax.devices()`` and
its HTTP ``GatewayServer``), with random weights made from a seed.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # a 4-chip host: the multi-chip phase

One chip, three phases:

* train: ``xlstm_350m`` at full size (24 layers, d_model 1024), seq 2048,
  global batch 8, microbatch 4.  The autostep engine drives 5 steps; the
  losses must be finite, and device memory must fall back once the block
  expires.
* serve: ``deepseek_7b`` at full width with 16 of its 30 layers (one 16 GB
  chip holds no more beside the KV pool), as a paged serve block: page 16,
  8 slots, max sequence 1024.  Four concurrent ``POST
  /v1/blocks/<id>/generate`` requests of 100-400 seeded prompt tokens must
  each get HTTP 200 and exactly 32 tokens.
* kernel: the Pallas paged-attention kernel at the serve block's shapes
  against ``kernels/ref.py`` attention in float32, and ``tpu_custom_call``
  in the compiled paged decode step (the kernel is really in the program).

``--chips 4`` runs only the multi-chip phase and what it is compared
with: the same train job sharded over a 4-chip block against a 1-chip
block of the same seed, then two tenants' 2-chip train blocks side by side,
each of whose arrays must stay on its own chips.

Informational lines come first.  On success the last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``.  Any failure,
including a JAX that finds no TPU, exits 1 without that line.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
import gc
import json
import math
import os
import sys
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
SEED = 0
TRAIN_STEPS = 5
GEN_TOKENS = 32
N_REQUESTS = 4
SERVE_LAYERS = 16
PAGE, SLOTS, MAX_SEQ = 16, 8, 1024
#: paged kernel (bf16 in and out) vs the float32 reference: outputs are
#: softmax-weighted means of unit-normal values, |o| < 1, so bf16 output
#: rounding stays under 2^-8; a masking or page-order fault is O(0.1)
KERNEL_TOL = 1e-2
#: 4-chip vs 1-chip losses (same seed): the sharded block reduces in a
#: different order and lowers its kernels through XLA
LOSS_RTOL = 1e-2
PHASE_DEADLINE_S = 480.0


class SmokeFailure(RuntimeError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileWatch:
    """Counts XLA compile time and persistent-cache hits/misses from JAX's
    own monitoring events."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def wait_terminal(daemon, app_id: str, deadline_s: float):
    from repro.core.block import BlockState
    terminal = (BlockState.DONE, BlockState.FAILED, BlockState.EXPIRED)
    blk = daemon.registry.get(app_id)
    t_end = time.monotonic() + deadline_s
    while blk.state not in terminal:
        check(time.monotonic() < t_end,
              f"{app_id} still {blk.state.value} after {deadline_s:.0f} s")
        time.sleep(0.05)
    return blk


def mem(device, key: str) -> int:
    """One ``memory_stats()`` counter (0 where the backend has none)."""
    return int((device.memory_stats() or {}).get(key, 0))


def block_devices(rt) -> set:
    import jax
    return {d for leaf in jax.tree.leaves(rt.state) for d in leaf.devices()}


def expire_and_check_memory(daemon, app_id: str, devices) -> None:
    """Expire a block and require its chips' memory to fall back."""
    before = [mem(d, "bytes_in_use") for d in devices]
    daemon.expire(app_id)
    gc.collect()
    after = [mem(d, "bytes_in_use") for d in devices]
    log(f"memory after expiring {app_id}: bytes_in_use "
        f"{before} -> {after}")
    for b, a in zip(before, after):
        check(a < max(b // 4, 1 << 30),
              f"device memory did not fall back after expiry: {b} -> {a}")


# ------------------------------------------------------------------ train

def train_job(seed: int = SEED):
    import repro.configs as configs
    from repro.core.runtime import JobSpec
    from repro.models.config import ShapeConfig
    from repro.train.optimizer import OptConfig
    cfg = configs.get("xlstm_350m")
    shape = ShapeConfig("smoke", "train", seq_len=2048, global_batch=8,
                        microbatch=4)
    return JobSpec(cfg, shape, seed=seed, collect_metrics=True,
                   opt=OptConfig(warmup_steps=1, total_steps=TRAIN_STEPS))


def run_train(daemon, watch, job, n_chips: int, user: str, inspect=None):
    """Submit one train block, let the autostep engine drive it to
    TRAIN_STEPS, check its losses, expire it.  Returns the losses."""
    c0, t0 = watch.compile_s, time.perf_counter()
    app, grant = daemon.submit(user, f"train {job.cfg.name}", n_chips,
                               job=job)
    check(grant is not None, f"{n_chips}-chip train block not admitted")
    rt = daemon.runtime(app)
    devices = list(rt.devices)
    daemon.autostep_enable(app, until_steps=TRAIN_STEPS)
    blk = wait_terminal(daemon, app, PHASE_DEADLINE_S)
    check(blk.state.value == "done",
          f"train block {app} ended {blk.state.value}: {blk.failure_reason}")
    wall = time.perf_counter() - t0
    steps = [e.payload for e in daemon.events_since(0, app_id=app,
                                                    kinds={"step"})]
    check(len(steps) == TRAIN_STEPS,
          f"{len(steps)} step events, expected {TRAIN_STEPS}")
    losses = [float(s["metrics"]["loss"]) for s in steps]
    step_s = [float(s["step_s"]) for s in steps]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    peak = [mem(d, "peak_bytes_in_use") for d in devices]
    tokens = job.shape.global_batch * job.shape.seq_len
    log(f"train {job.cfg.name} on {n_chips} chip(s) {[d.id for d in devices]}"
        f": submit-to-done {wall:.2f} s, XLA compile "
        f"{watch.compile_s - c0:.2f} s")
    log(f"  first step (compile + run) {step_s[0]:.3f} s; steps after the "
        f"first, each closed when its outputs were ready on device: "
        f"{[round(s, 4) for s in step_s[1:]]} s "
        f"({tokens / (sum(step_s[1:]) / len(step_s[1:])):.0f} tokens/s)")
    log(f"  losses {losses}")
    log(f"  peak_bytes_in_use per chip {peak}")
    if inspect is not None:
        inspect(rt)
    del rt
    expire_and_check_memory(daemon, app, devices)
    return losses


# ------------------------------------------------------------------ serve

def serve_config():
    import repro.configs as configs
    full = configs.get("deepseek_7b")
    cfg = dataclasses.replace(full, n_layers=SERVE_LAYERS)
    log(f"reduced: deepseek_7b n_layers {full.n_layers} -> {SERVE_LAYERS} "
        f"(one 16 GB chip holds no more beside the {SLOTS} x {MAX_SEQ} KV "
        f"pool); d_model {cfg.d_model}, heads, d_ff and vocab unchanged")
    return cfg


def post_generate(url: str, token: str, prompt, timeout_s: float = 120.0):
    body = json.dumps({"prompt": prompt, "max_new_tokens": GEN_TOKENS,
                       "stream": False, "timeout_s": 30}).encode()
    req = urllib.request.Request(url, data=body, method="POST", headers={
        "Authorization": f"Bearer {token}",
        "Content-Type": "application/json"})
    t0 = time.perf_counter()
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as r:
            return r.status, json.loads(r.read()), time.perf_counter() - t0
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode()}, time.perf_counter() - t0


def wait_sessions(daemon, app: str, sids, cursor: int, deadline_s: float):
    """Block until every session in ``sids`` has finished (bus events)."""
    pending, t_end = set(sids), time.monotonic() + deadline_s
    while pending:
        blk = daemon.registry.get(app)
        check(blk.state.value in ("running", "active"),
              f"serve block {app} is {blk.state.value}: "
              f"{blk.failure_reason}")
        check(time.monotonic() < t_end,
              f"sessions {sorted(pending)} unfinished after {deadline_s} s")
        for ev in daemon.wait_events(cursor, app_id=app, kinds={"session"},
                                     timeout=1.0):
            cursor = ev.seq
            if ev.payload.get("action") == "finished":
                pending.discard(ev.payload.get("session"))
    return cursor


def run_serve(daemon, watch, gateway, user: str, token: str):
    import numpy as np
    from repro.core.runtime import JobSpec
    from repro.models.config import ShapeConfig
    cfg = serve_config()
    job = JobSpec(cfg, ShapeConfig("smoke", "serve", seq_len=MAX_SEQ,
                                   global_batch=SLOTS),
                  kind="serve", paged=True, page_size=PAGE, max_slots=SLOTS,
                  max_seq_len=MAX_SEQ, seed=SEED)
    c0, t0 = watch.compile_s, time.perf_counter()
    # the HTTP submit takes no layer cut, so the block is submitted
    # through the daemon as the gateway user, who then owns it over HTTP
    app, grant = daemon.submit(user, f"serve {cfg.name}", 1, job=job)
    check(grant is not None, "serve block not admitted")
    rt = daemon.runtime(app)
    device = rt.devices[0]
    log(f"serve block {app} on chip {device.id}: activated in "
        f"{time.perf_counter() - t0:.2f} s")

    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=int(rng.integers(100, 401))).tolist()
               for _ in range(N_REQUESTS)]
    log(f"  prompt lengths {[len(p) for p in prompts]}")
    # warm-up: the same prompts once through the daemon, so every prompt
    # bucket's prefill and the decode step compile before the measured
    # requests (compilation is set-up, not request latency)
    t1 = time.perf_counter()
    cursor = daemon.bus.latest_seq
    warm = [daemon.generate(app, p, max_new_tokens=GEN_TOKENS)
            for p in prompts]
    daemon.autostep_enable(app)
    wait_sessions(daemon, app, warm, cursor, PHASE_DEADLINE_S)
    log(f"  warm-up generate (compiles included) {time.perf_counter() - t1:.2f}"
        f" s; XLA compile so far {watch.compile_s - c0:.2f} s")

    url = f"{gateway.url}/v1/blocks/{app}/generate"
    t2 = time.perf_counter()
    with concurrent.futures.ThreadPoolExecutor(N_REQUESTS) as ex:
        results = list(ex.map(lambda p: post_generate(url, token, p),
                              prompts))
    wall = time.perf_counter() - t2
    for i, (status, out, dt) in enumerate(results):
        check(status == 200, f"request {i}: HTTP {status} {out}")
        check(out.get("done") and len(out.get("tokens", ())) == GEN_TOKENS,
              f"request {i}: {len(out.get('tokens', ()))} tokens, "
              f"done={out.get('done')}")
        check(all(0 <= t < cfg.vocab_size for t in out["tokens"]),
              f"request {i}: token id out of range")
    sch = rt.sessions
    ttft = [sch.sessions[out["session"]].first_token_t
            - sch.sessions[out["session"]].submitted_t
            for _, out, _ in results]
    same = [out["tokens"] == sch.sessions[sid].generated
            for (_, out, _), sid in zip(results, warm)]
    log(f"  {N_REQUESTS} concurrent HTTP generate requests: all 200, "
        f"{GEN_TOKENS} tokens each, wall {wall:.3f} s")
    log(f"  info (not a metric): latency per request "
        f"{[round(r[2], 3) for r in results]} s, server-side TTFT "
        f"{[round(t, 3) for t in ttft]} s, "
        f"{N_REQUESTS * GEN_TOKENS / wall:.1f} generated tokens/s; greedy "
        f"tokens equal to the warm-up run's: {same}")
    log(f"  peak_bytes_in_use {mem(device, 'peak_bytes_in_use')}")

    kernel_check(cfg, rt)
    del rt, sch
    expire_and_check_memory(daemon, app, [device])


def kernel_check(cfg, rt) -> None:
    """Pallas paged attention at the serve block's shapes vs the float32
    reference, and the kernel's presence in the compiled decode step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    a = cfg.attention
    maxp = MAX_SEQ // PAGE
    n_pages = SLOTS * maxp + 1
    dev = rt.devices[0]
    ks = jax.random.split(jax.random.PRNGKey(SEED), 3)
    dt = jnp.dtype(cfg.param_dtype)
    q = jax.random.normal(ks[0], (SLOTS, a.n_heads, 1, a.head_dim), dt)
    k_pages = jax.random.normal(ks[1], (n_pages, PAGE, a.n_kv_heads,
                                        a.head_dim), dt)
    v_pages = jax.random.normal(ks[2], (n_pages, PAGE, a.n_kv_heads,
                                        a.v_dim), dt)
    # poisoned trash page: a masking fault would dominate the error
    k_pages = k_pages.at[0].set(100.0)
    v_pages = v_pages.at[0].set(-100.0)
    # ragged fills: one token, mid-page, page boundaries, a full slot
    lens = np.array([1, PAGE + 1, MAX_SEQ // 10, MAX_SEQ // 4 - 1,
                     MAX_SEQ // 4, MAX_SEQ // 2 + 1, MAX_SEQ - 24,
                     MAX_SEQ][:SLOTS], np.int32)
    rng = np.random.default_rng(SEED)
    free = list(rng.permutation(np.arange(1, n_pages)))
    table = np.zeros((SLOTS, maxp), np.int32)
    for b, n in enumerate(lens):
        for j in range(-(-int(n) // PAGE)):
            table[b, j] = free.pop()
    q, k_pages, v_pages, tbl, sl = jax.device_put(
        (q, k_pages, v_pages, table, lens), dev)
    attend = jax.jit(ops.paged_attention)
    got = np.asarray(attend(q, k_pages, v_pages, tbl, sl), np.float32)
    check("tpu_custom_call" in attend.lower(q, k_pages, v_pages, tbl, sl)
          .compile().as_text(), "paged_attention did not lower to Pallas")
    err = 0.0
    with jax.default_matmul_precision("highest"):
        for b, n in enumerate(lens):
            kd = k_pages[tbl[b]].reshape(1, -1, a.n_kv_heads, a.head_dim)
            vd = v_pages[tbl[b]].reshape(1, -1, a.n_kv_heads, a.v_dim)
            want = ref.attention(
                q[b:b + 1].astype(jnp.float32),
                kd[:, :n].swapaxes(1, 2).astype(jnp.float32),
                vd[:, :n].swapaxes(1, 2).astype(jnp.float32), causal=False)
            err = max(err, float(np.max(np.abs(got[b:b + 1]
                                               - np.asarray(want)))))
    log(f"kernel: paged_attention (Pallas, {dt.name}) vs ref.attention "
        f"float32 at B={SLOTS} H={a.n_heads} D={a.head_dim} page={PAGE} "
        f"pages/seq={maxp}, lens {lens.tolist()}: max abs err {err:.3e} "
        f"(tolerance {KERNEL_TOL:.0e})")
    check(err <= KERNEL_TOL, f"paged kernel error {err} > {KERNEL_TOL}")

    sch = rt.sessions
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                          sharding=x.sharding)
    args = jax.tree.map(spec, (sch.params, sch.last_tokens_dev, sch.pool))
    small = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=rt.replicated)
             for x in (sch.page_table, sch.seq_lens)]
    text = sch._decode.lower(*args, *small).compile().as_text()
    check("tpu_custom_call" in text,
          "compiled paged decode step has no tpu_custom_call")
    log("kernel: compiled paged decode step contains tpu_custom_call")


# -------------------------------------------------------------- four chips

def run_four_chips(daemon, watch) -> None:
    import numpy as np
    job = train_job()
    one = run_train(daemon, watch, job, 1, "ref-1chip")

    def sharded_on_four(rt):
        params = rt.state["params"]
        devs = block_devices(rt)
        import jax
        split = [leaf for leaf in jax.tree.leaves(params)
                 if not leaf.sharding.is_fully_replicated]
        log(f"  4-chip block: state on devices "
            f"{sorted(d.id for d in devs)}, {len(split)} of "
            f"{len(jax.tree.leaves(params))} parameter leaves sharded")
        check(len(devs) == 4, f"state on {len(devs)} devices, not 4")
        check(split, "no parameter is sharded over the 4-chip mesh")

    four = run_train(daemon, watch, job, 4, "sharded-4chip",
                     inspect=sharded_on_four)
    rel = float(np.max(np.abs(np.array(four) - np.array(one))
                       / np.abs(np.array(one))))
    log(f"4-chip vs 1-chip losses: max relative difference {rel:.3e} "
        f"(tolerance {LOSS_RTOL:.0e})")
    check(rel <= LOSS_RTOL, f"4-chip losses {four} vs 1-chip {one}")

    # two tenants side by side, 2 chips each
    apps = {}
    for user, seed in (("tenant-a", SEED), ("tenant-b", SEED + 1)):
        app, grant = daemon.submit(user, "train xlstm_350m", 2,
                                   job=train_job(seed))
        check(grant is not None, f"{user}'s 2-chip block not admitted")
        apps[app] = set(daemon.runtime(app).devices)
    for app in apps:
        daemon.autostep_enable(app, until_steps=TRAIN_STEPS)
    t0 = time.perf_counter()
    owned = {}
    for app, granted in apps.items():
        rt = daemon.runtime(app)
        on = block_devices(rt)
        owned[app] = on
        check(on <= granted, f"{app} has arrays on {on - granted}, outside "
                             f"its grant {granted}")
    a, b = apps
    check(not (apps[a] & apps[b]), "the two blocks share a chip")
    for app in apps:
        blk = wait_terminal(daemon, app, PHASE_DEADLINE_S)
        check(blk.state.value == "done",
              f"{app} ended {blk.state.value}: {blk.failure_reason}")
        losses = [e.payload["metrics"]["loss"] for e in
                  daemon.events_since(0, app_id=app, kinds={"step"})]
        check(len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)),
              f"{app} losses {losses}")
        log(f"side by side: {app} on chips "
            f"{sorted(d.id for d in apps[app])}, arrays on "
            f"{sorted(d.id for d in owned[app])}, losses {losses}")
    log(f"  both blocks done {time.perf_counter() - t0:.2f} s after arming")
    for app in apps:
        expire_and_check_memory(daemon, app, sorted(apps[app],
                                                    key=lambda d: d.id))


# ------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip phase on a 4-chip host")
    args = ap.parse_args(argv)
    if args.chips == 4:
        # the TPU compiler's latency-hiding scheduler fails a RET_CHECK
        # ("not scheduled after its control predecessor") on the xlstm_350m
        # train step sharded over a 2x2 mesh at microbatch 4; with it off
        # the step compiles (AOT compile for a described v5e:2x2)
        os.environ["LIBTPU_INIT_ARGS"] = (
            os.environ.get("LIBTPU_INIT_ARGS", "")
            + " --xla_tpu_enable_latency_hiding_scheduler=false").strip()

    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.core import peaks
    from repro.core.daemon import ClusterDaemon
    from repro.core.topology import host_topology
    from repro.gateway import GatewayServer, ProfileStore, UserProfile
    from repro.train import compile_cache

    cache_dir = compile_cache.use_persistent_cache()
    devices = jax.devices()
    dev0 = devices[0]
    check(dev0.platform == "tpu",
          f"JAX found no TPU (platform {dev0.platform!r}); this check runs "
          f"only on the chip")
    check(len(devices) >= args.chips,
          f"--chips {args.chips} needs {args.chips} chips, have "
          f"{len(devices)}")
    chip = peaks.peaks_for(dev0.device_kind)
    log(f"device: {dev0.platform} {dev0.device_kind!r} x{len(devices)}; "
        f"peaks {chip.bf16_flops:.3g} FLOP/s bf16, {chip.hbm_bw:.3g} B/s "
        f"HBM")
    log(f"compile cache: {cache_dir} "
        f"({len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0}"
        f" entries at start)")
    watch = CompileWatch(jax)

    topo, ordered = host_topology(devices)
    daemon = ClusterDaemon(topo, devices=ordered, background=True,
                           ckpt_root=os.path.join(ROOT, "artifacts",
                                                  "chip_smoke"))
    token = "chip-smoke-token"
    gateway = GatewayServer(daemon, ProfileStore(
        [UserProfile("smoke", token)]), host="127.0.0.1", port=0).start()
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips(daemon, watch)
        else:
            run_train(daemon, watch, train_job(), 1, "smoke")
            run_serve(daemon, watch, gateway, "smoke", token)
    finally:
        gateway.stop()
        daemon.stop()
    log(f"total {time.perf_counter() - t0:.1f} s; XLA compile "
        f"{watch.compile_s:.1f} s; persistent cache hits {watch.hits}, "
        f"misses {watch.misses}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev0.platform, "kind": dev0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except Exception as e:       # every failure: a message and exit 1
        import traceback
        traceback.print_exc()
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        sys.exit(1)
