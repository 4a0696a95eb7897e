"""Readings that the limits of ``correct`` are set from, and the serving
knee.  Runs on the chip, in one process, so that set-up is paid once.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        --control-seeds 7,8,9 [--seconds 25] [--out readings.json]
    python3 bench/calibrate.py --workload <serve cell> --rates 1,1.5,2 \\
        --seconds 40

For each seed of ``--seeds``: the program's numbers against the float32
reference, as a run computes them.  For each of ``--control-seeds``: the
control (the reference in float8, put in the program's place) against the
reference, and for a train cell the fault of half the batch left out, the
mean taken over the rest, and a witness: the reference with its matrix
products' operands in bfloat16, the configuration's own precision.  ``--rates`` instead offers the cell's serve mix
at each rate in turn to the cell's serve block, with the cell's train
blocks stepping beside it under autostep, and reports the tails, the tokens
completed per second, the KV pages free and the train blocks' tokens/s in
the window, to find the knee and the share of the pool that the mix
fills."""
from __future__ import annotations

import argparse
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import check, load, run, system      # noqa: E402


#: the tails a sweep reports at each rate
SWEEP_TAILS = ("ttft_p50_s", "ttft_p80_s", "ttft_p90_s", "itl_p50_s",
               "itl_p95_s")


def _ints(s):
    return [int(x) for x in s.split(",") if x]


def _judged(blk, nums) -> bool:
    """What a run's ``correct`` would read on these numbers under the
    configuration's limits as they stand."""
    return check.judge(nums, blk.cfg["limits"],
                       blk.cfg.get("not_compared", {}))[1]


def train_readings(daemon, blk, seeds, control_seeds, out):
    import jax
    refs = {}
    for seed in seeds:
        t0 = time.perf_counter()
        prog = run.setup_train(daemon, blk, seed, arm=False)
        daemon.expire(blk.app)
        gc.collect()
        t1 = time.perf_counter()
        with jax.default_device(blk.devices[0]):
            refs[seed] = check.train_reference(blk.cfg, blk.mix, seed)
        nums, notes = check.train_numbers(prog, refs[seed],
                                          blk.cfg.get("direction_leaf"))
        notes["correct"] = _judged(blk, nums)
        if seed not in control_seeds:
            del refs[seed]
        rec = {"seed": seed, "who": "program", **nums, **notes,
               "prog_losses": prog["losses"],
               "prog_grad_norm": prog["grad_norms"],
               "program_s": t1 - t0, "reference_s": time.perf_counter() - t1}
        out.append(rec)
        print(json.dumps(rec), flush=True)
    for seed in control_seeds:
        with jax.default_device(blk.devices[0]):
            ref = refs.get(seed) or check.train_reference(blk.cfg, blk.mix,
                                                          seed)
            for who, kw in (("control_fp8", {"prec": "fp8"}),
                            ("fault_half_batch",
                             {"rows": blk.mix["global_batch"] // 2}),
                            ("witness_bf16", {"prec": "bf16"})):
                t0 = time.perf_counter()
                other = check.train_reference(blk.cfg, blk.mix, seed, **kw)
                nums, notes = check.train_numbers(
                    other, ref, blk.cfg.get("direction_leaf"))
                notes["correct"] = _judged(blk, nums)
                rec = {"seed": seed, "who": who, **nums, **notes,
                       "seconds": time.perf_counter() - t0}
                out.append(rec)
                print(json.dumps(rec), flush=True)


def serve_window(daemon, gateway, blk, seed, seconds, rate=None,
                 watch=None):
    """One window of the cell's serve mix (at ``rate`` if given) on the
    block; returns the Load once the window's requests have ended.
    ``watch``, if given, is called every quarter second of the window."""
    mix = dict(blk.mix)
    if rate is not None:
        mix["rate_per_s"] = rate
    reqs = load.serve_requests(mix, blk.cfg["vocab_size"], seed, seconds,
                               run.TAIL_S)
    ld = run.Load(gateway.port, blk.app, reqs)
    w0 = time.perf_counter()
    ld.start(w0)
    while (left := w0 + seconds - time.perf_counter()) > 0:
        if watch is not None:
            watch()
        time.sleep(min(0.25, left))
    ld.wait_measured(w0 + seconds + run.DRAIN_S)
    ld.close()
    return ld, w0


def serve_readings(daemon, gateway, blk, seeds, control_seeds, seconds, out):
    import jax
    for seed in sorted(set(seeds) | set(control_seeds),
                       key=lambda s: (s not in seeds, s)):
        t0 = time.perf_counter()
        run.setup_serve(daemon, blk, seed, gateway)
        ld, _ = serve_window(daemon, gateway, blk, seed, seconds)
        sample = run.serve_sample(ld, seed)
        sm = run.serve_metrics(ld)
        daemon.expire(blk.app)
        gc.collect()
        t1 = time.perf_counter()
        with jax.default_device(blk.devices[0]):
            gaps = check.serve_reference(blk.cfg, seed, sample)
            rec = {"seed": seed, "who": "program",
                   **check.serve_numbers(gaps), "tokens": len(gaps),
                   "failed": sm["failed"], "n": sm["n"],
                   "program_s": t1 - t0,
                   "reference_s": time.perf_counter() - t1}
            rec["correct"] = _judged(blk, check.serve_numbers(gaps))
            if seed in seeds:
                out.append(rec)
                print(json.dumps(rec), flush=True)
            if seed in control_seeds:
                t2 = time.perf_counter()
                ctl = check.serve_reference(blk.cfg, seed, sample, "fp8")
                rec = {"seed": seed, "who": "control_fp8",
                       **check.serve_numbers(ctl), "tokens": len(ctl),
                       "seconds": time.perf_counter() - t2,
                       "correct": _judged(blk, check.serve_numbers(ctl))}
                out.append(rec)
                print(json.dumps(rec), flush=True)


def sweep(daemon, gateway, blk, trains, rates, seconds, out):
    run.setup_serve(daemon, blk, 0, gateway)
    for b in trains:
        run.setup_train(daemon, b, 0)
    rt = daemon.runtime(blk.app)
    for i, rate in enumerate(rates):
        before = dict(rt.sessions.stats())
        free = []
        ld, w0 = serve_window(
            daemon, gateway, blk, 1000 + i, seconds, rate,
            watch=lambda: free.append(rt.sessions.stats()["free_pages"]))
        sm = run.serve_metrics(ld, SWEEP_TAILS)
        toks = sum(1 for s in ld.sent for t in s.times
                   if w0 <= t <= w0 + seconds)
        wall0 = w0 + time.time() - time.perf_counter()
        rec = {"rate_per_s": rate, **sm,
               "tokens_per_s_in_window": toks / seconds,
               "train_tokens_per_s": {
                   b.tenant: run.train_rate(daemon, b, wall0,
                                            wall0 + seconds)[0]
                   for b in trains},
               # the KV pool's free pages, read every quarter second of
               # the window: the pool in use is the total less these
               "free_pages_min": min(free),
               "free_pages_median": statistics.median(free),
               "unfinished": sum(1 for s in ld.measured if not s.done),
               "scheduler_before": before,
               "scheduler_after": dict(rt.sessions.stats())}
        out.append(rec)
        print(json.dumps(rec), flush=True)
        # let the tail drain before the next rate
        t_end = time.monotonic() + 120
        while rt.sessions.has_work and time.monotonic() < t_end:
            time.sleep(0.2)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, default=[])
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--rates", type=lambda s: [float(x) for x in
                                               s.split(",") if x])
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--out")
    ap.add_argument("--bench", default=os.path.join(ROOT, "BENCHMARK.json"),
                    help="the file that lists the cell (a cell not yet in "
                         "BENCHMARK.json is read before it is added)")
    args = ap.parse_args(argv)
    bench = run.read_json(args.bench)
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    blocks = run.cell_blocks(os.path.join(ROOT, "bench"), cell)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibration reads the chip: no TPU found")
    run.use_checkout_cache(jax)
    system.import_program(ROOT)
    daemon = system.start_daemon(jax.devices()[:cell["chips"]],
                                 os.path.join(ROOT, ".bench_out", "ckpt"))
    serve = next((b for b in blocks if b.kind == "serve"), None)
    gateway = (system.start_gateway(daemon, serve.tenant, run.TOKEN)
               if serve is not None else None)
    out = []
    try:
        if args.rates:
            sweep(daemon, gateway, serve,
                  [b for b in blocks if b.kind == "train"], args.rates,
                  args.seconds, out)
        elif serve is None:
            train_readings(daemon, blocks[0], args.seeds,
                           args.control_seeds, out)
        else:
            serve_readings(daemon, gateway, serve, args.seeds,
                           args.control_seeds, args.seconds, out)
    finally:
        if gateway is not None:
            gateway.stop()
        daemon.stop()
    if args.out:
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
