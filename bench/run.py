"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process, on the chips JAX finds:
builds the program's ``ClusterDaemon`` (and its HTTP gateway when the cell
serves), submits the cell's blocks as tenants do, warms every shape the
cell's traffic uses, measures for ``--seconds``, checks what the timed path
produced against the plain reference, and prints one JSON line last.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name the cell gives:
``bench/configs/<config>.json``, ``bench/traffic/<mix>.json`` (read by
``bench/load.py``) and ``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import concurrent.futures
import dataclasses
import gc
import http.client
import importlib.util
import json
import os
import re
import shutil
import statistics
import sys
import threading
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import check, load, system          # noqa: E402
from bench import trace as trace_lib           # noqa: E402
from bench.peaks import peaks_for              # noqa: E402

TOKEN = "bench-tenant-token"
#: past the window's close, how long a due request may still take to end
DRAIN_S = 60.0
#: requests of the mix sent after the window, to keep the load on while
#: the window's last requests finish
TAIL_S = 60.0
#: a traced run profiles this many seconds in the middle of its window
#: (a profile of a whole window of training holds some ten million ops)
TRACE_S = 12.0


class NoChip(RuntimeError):
    """The run cannot measure here (no accelerator, too few chips, a
    device kind without published peaks)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ spec

def read_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Block:
    tenant: str
    kind: str                 # train | serve
    cfg: Dict
    mix: Dict
    app: Optional[str] = None
    devices: tuple = ()


def cell_blocks(bench_dir: str, cell: Dict) -> List[Block]:
    """The cell's tenants: one block for a model configuration, or the
    blocks a deployment configuration lists; each with its traffic."""
    cfg = read_json(os.path.join(bench_dir, "configs",
                                 cell["config"] + ".json"))
    mix = read_json(os.path.join(bench_dir, "traffic",
                                 cell["traffic"] + ".json"))

    def mix_of(kind):
        if mix["kind"] == kind:
            return mix
        return read_json(os.path.join(bench_dir, "traffic",
                                      mix[kind] + ".json"))

    if "blocks" not in cfg:
        return [Block("tenant-0", mix["kind"], cfg, mix)]
    out = []
    for b in cfg["blocks"]:
        sub = read_json(os.path.join(bench_dir, "configs",
                                     b["config"] + ".json"))
        out.append(Block(b["tenant"], b["kind"], sub, mix_of(b["kind"])))
    return out


# --------------------------------------------------------------- compile

class CompileWatch:
    """Counts XLA compilations and persistent-cache hits from JAX's own
    monitoring events, with the time of each compilation."""

    def __init__(self, jax):
        self.times: List[float] = []
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.times.append(time.perf_counter())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def between(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 <= t <= t1)


def use_checkout_cache(jax) -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------------ train

def wait_step_events(daemon, app: str, n: int, timeout: float = 900.0):
    t_end = time.monotonic() + timeout
    while True:
        evs = daemon.events_since(0, app_id=app, kinds={"step"})
        if len(evs) >= n:
            return evs
        blk = daemon.registry.get(app)
        if blk.state.value not in ("running", "active"):
            raise RuntimeError(f"train block {app} is {blk.state.value}: "
                               f"{blk.failure_reason}")
        if time.monotonic() > t_end:
            raise RuntimeError(f"{app}: {len(evs)} of {n} steps after "
                               f"{timeout} s")
        time.sleep(0.01)


def setup_train(daemon, blk: Block, seed: int, arm: bool = True) -> Dict:
    """Submit the train block, drive its first three steps through the
    engine's own dispatch and feed, read what the reference is held to, and
    arm autostep for the window.  Returns the program's readings."""
    import jax
    job = system.train_job(blk.cfg, blk.mix, seed)
    blk.app, grant = daemon.submit(blk.tenant, f"train {blk.cfg['name']}",
                                   1, job=job)
    if grant is None:
        raise RuntimeError(f"{blk.tenant}: train block not admitted")
    rt = daemon.runtime(blk.app)
    blk.devices = tuple(rt.devices)
    p0 = jax.tree.map(lambda x: x.copy(), rt.state["params"])
    grad_leaf = None
    for k in range(3):
        daemon.run_steps({blk.app: 1})
        if k == 0:
            b1 = blk.cfg["optimizer"]["b1"]
            m = jax.tree.leaves(rt.state["opt"]["m"])
            grad_leaf = [x / (1 - b1) for x in check.leaf_norms(m)]
            # to host: the reference runs once the block has freed the chip
            grad_first = [x / (1 - b1) for x in jax.device_get(m)]
    change = check.diff_norms(rt.state["params"], p0)
    del p0
    evs = wait_step_events(daemon, blk.app, 3)
    metrics = [e.payload["metrics"] for e in evs[:3]]
    if arm:
        daemon.autostep_enable(blk.app)
    return {"losses": [float(m["loss"]) for m in metrics],
            "grad_norms": [float(m["grad_norm"]) for m in metrics],
            "grad_leaf": grad_leaf, "change_leaf": change,
            "grad_first": grad_first}


def train_rate(daemon, blk: Block, w0: float, w1: float):
    """(tokens/s, steps): whole steps that completed inside the window,
    from the first one's end to the last one's end, in step events the
    tenant's feed carries (wall clock)."""
    evs = [e for e in daemon.events_since(0, app_id=blk.app, kinds={"step"})
           if w0 <= e.t <= w1]
    tokens = blk.mix["global_batch"] * blk.mix["seq_len"]
    if len(evs) < 2:
        return None, len(evs)
    return (len(evs) - 1) * tokens / (evs[-1].t - evs[0].t), len(evs)


# ------------------------------------------------------------------ serve

@dataclasses.dataclass
class Sent:
    req: load.Request
    t_due: float = 0.0
    t_send: float = 0.0
    status: int = 0
    error: Optional[str] = None
    session: Optional[str] = None
    tokens: List[int] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)
    done: bool = False


def stream_generate(port: int, app: str, rec: Sent,
                    closing: threading.Event) -> None:
    """One tenant request: POST generate, read the SSE stream, stamp every
    token as it arrives."""
    body = json.dumps({"prompt": rec.req.prompt,
                       "max_new_tokens": rec.req.max_new_tokens,
                       "stream": True}).encode()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=DRAIN_S * 4)
    try:
        rec.t_send = time.perf_counter()
        conn.request("POST", f"/v1/blocks/{app}/generate", body=body,
                     headers={"Authorization": f"Bearer {TOKEN}",
                              "Content-Type": "application/json"})
        resp = conn.getresponse()
        rec.status = resp.status
        if resp.status != 200:
            rec.error = resp.read()[:200].decode(errors="replace")
            return
        event, data = None, None
        while not closing.is_set():
            line = resp.readline()
            if not line:
                break
            line = line.decode().rstrip("\r\n")
            if line.startswith("event:"):
                event = line[6:].strip()
            elif line.startswith("data:"):
                data = line[5:].strip()
            elif line == "" and event is not None:
                t = time.perf_counter()
                p = json.loads(data) if data else {}
                rec.session = rec.session or p.get("session")
                if event == "generate":
                    rec.tokens.append(int(p["token"]))
                    rec.times.append(t)
                    if p.get("done"):
                        rec.done = True
                        break
                elif p.get("action") == "finished":
                    rec.done = True
                    break
                event, data = None, None
    except Exception as e:       # a request that fails counts as failed
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        conn.close()


def warm_serve(daemon, blk: Block) -> int:
    """Every prefill bucket the mix's prompt lengths can reach, and the
    decode step, through the daemon's generate path."""
    page = blk.cfg["serve"]["page_size"]
    lengths = load.prefill_buckets(blk.mix, page)
    cursor = daemon.bus.latest_seq
    sids = {daemon.generate(blk.app, [1 + (i % 97)] * n, max_new_tokens=2)
            for i, n in enumerate(lengths)}
    daemon.autostep_enable(blk.app)
    t_end = time.monotonic() + 1200
    while sids:
        for ev in daemon.wait_events(cursor, app_id=blk.app,
                                     kinds={"session"}, timeout=1.0):
            cursor = ev.seq
            if ev.payload.get("action") == "finished":
                sids.discard(ev.payload.get("session"))
        if time.monotonic() > t_end:
            raise RuntimeError(f"warm-up unfinished: {len(sids)} sessions")
    return len(lengths)


def setup_serve(daemon, blk: Block, seed: int, gateway) -> Dict:
    job = system.serve_job(blk.cfg, seed)
    blk.app, grant = daemon.submit(blk.tenant, f"serve {blk.cfg['name']}",
                                   1, job=job)
    if grant is None:
        raise RuntimeError(f"{blk.tenant}: serve block not admitted")
    rt = daemon.runtime(blk.app)
    blk.devices = tuple(rt.devices)
    n_buckets = warm_serve(daemon, blk)
    # one request through the gateway too, so that its code paths are warm
    rec = Sent(load.Request(-1, 0.0, [3] * 40, 4, False))
    stream_generate(gateway.port, blk.app, rec, threading.Event())
    if not rec.done:
        raise RuntimeError(f"gateway warm-up request failed: {rec.error}")
    return {"buckets": n_buckets}


class Load:
    """Open-loop sender: each request is sent at its due time on a thread
    of its own, whatever the server is doing."""

    def __init__(self, port: int, app: str, reqs: List[load.Request]):
        self.port, self.app = port, app
        self.sent = [Sent(r) for r in reqs]
        self.closing = threading.Event()
        self.stop = threading.Event()
        self.pool = concurrent.futures.ThreadPoolExecutor(64)
        self.thread = threading.Thread(target=self._run, daemon=True)

    def start(self, t0: float) -> None:
        self.t0 = t0
        for s in self.sent:
            s.t_due = t0 + s.req.due_s
        self.thread.start()

    def _run(self) -> None:
        for s in self.sent:
            while not self.stop.is_set():
                dt = s.t_due - time.perf_counter()
                if dt <= 0:
                    break
                self.stop.wait(min(dt, 0.05))
            if self.stop.is_set():
                return
            self.pool.submit(stream_generate, self.port, self.app, s,
                             self.closing)

    @property
    def measured(self) -> List[Sent]:
        return [s for s in self.sent if s.req.measured]

    def wait_measured(self, deadline: float) -> None:
        while time.perf_counter() < deadline:
            if all(s.done or s.error for s in self.measured):
                return
            time.sleep(0.05)

    def close(self) -> None:
        self.stop.set()
        self.closing.set()
        self.thread.join(5)
        self.pool.shutdown(wait=True, cancel_futures=True)


def quantile(xs, q: float) -> float:
    """The q-th quantile (0 < q < 1) by linear interpolation between order
    statistics, as ``statistics.quantiles(method="inclusive")`` puts them."""
    xs = sorted(xs)
    if len(xs) == 1:
        return xs[0]
    pos = q * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


#: a serve cell's tails, named by what they read and their percentile:
#: ``ttft_p80_s`` is the 80th percentile of the times to first token
TAIL_METRIC = re.compile(r"(ttft|itl)_p(\d+)_s$")


def serve_metrics(ld: Load, names=()):
    """The tails that ``names`` ask for (as ``TAIL_METRIC`` reads a name)
    over the window's requests, with their counts.  A time to first token
    runs from the request's due time; a request that failed counts as
    infinite.  The inter-token gaps are those between a request's
    successive SSE tokens."""
    meas = ld.measured
    samples = {
        "ttft": [(s.times[0] - s.t_due) if (s.done and s.times)
                 else float("inf") for s in meas],
        "itl": [b - a for s in meas for a, b in zip(s.times, s.times[1:])]}
    failed = sum(1 for s in meas if not s.done)
    late = [s.t_send - s.t_due for s in meas if s.t_send]
    out = {"n": len(meas), "failed": failed, "n_gaps": len(samples["itl"]),
           "late_max_s": max(late) if late else None,
           "late_p50_s": statistics.median(late) if late else None}
    for name in names:
        m = TAIL_METRIC.match(name)
        if m:
            xs = samples[m[1]]
            out[name] = quantile(xs, int(m[2]) / 100) if xs else float("inf")
    return out


def serve_sample(ld: Load, seed: int, n: int = 8):
    """Finished measured requests to compare: the longest, and others drawn
    from the seed."""
    import numpy as np
    done = [s for s in ld.measured if s.done and s.tokens]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.req.prompt) + len(s.tokens))
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng(seed + 1)
    pick = [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [(s.req.prompt, s.tokens) for s in [longest] + pick]


# ----------------------------------------------------------- per layer

@dataclasses.dataclass
class RunData:
    """What a per-layer reader may read."""
    blocks: List[Block]
    peaks: object
    w0: float                      # window, host perf_counter
    w1: float
    tw0: float                     # the traced part of it
    tw1: float
    spans: List                    # (name, t0, t1, args, app_id, parent)
    span_by_id: Dict
    trace: Optional[Dict] = None   # bench.trace.load output
    to_trace: Optional[object] = None
    load: Optional[Load] = None
    sessions: Optional[Dict] = None

    def devices_of(self, kind: str) -> List[str]:
        """Trace plane names of the chips that ``kind`` blocks hold."""
        if self.trace is None:
            return []
        ids = {d.id for b in self.blocks if b.kind == kind
               for d in b.devices}
        return [p for p in self.trace["devices"]
                if p.rsplit(":", 1)[-1].isdigit()
                and int(p.rsplit(":", 1)[-1]) in ids]

    def trace_window(self):
        return self.to_trace(self.tw0), self.to_trace(self.tw1)

    def block_of(self, kind: str) -> Optional[Block]:
        return next((b for b in self.blocks if b.kind == kind), None)


def load_reader(bench_dir: str, name: str):
    path = os.path.join(bench_dir, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(bench: Dict, cell: str, section: str, e2e_names):
    """The metrics of ``section`` that this cell reports: those that list
    it, and those with no list whose end-to-end metric it reports."""
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in e2e_names:
            out.append(m)
    return out


# ------------------------------------------------------------------ main

def device_summary(jax, devices, used) -> Dict:
    d0 = devices[0]
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in used)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def run(argv=None, *, require_tpu: bool = True,
        bench_path: Optional[str] = None,
        cells_dir: Optional[str] = None) -> int:
    """One run.  ``require_tpu=False``, another ``bench_path`` and a
    ``cells_dir`` holding other configs/ and traffic/ are for the tests,
    which drive the harness on the CPU at a small size."""
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_path = bench_path or os.path.join(ROOT, "BENCHMARK.json")
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    bench = read_json(bench_path)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no workload {args.workload!r} in {bench_path}")
    blocks = cell_blocks(cells_dir or bench_dir, cell)
    e2e_decl = cell_metrics(bench, args.workload, "end_to_end", ())
    # the program's keys take 32 bits; larger seeds wrap
    pseed = args.seed % (1 << 32)

    import jax
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform "
                     f"{devices[0].platform!r})")
    if len(devices) < cell["chips"]:
        raise NoChip(f"{cell['chips']} chips needed, {len(devices)} found")
    try:
        peaks = peaks_for(devices[0].device_kind if require_tpu
                          else "TPU v5 lite")
    except KeyError as e:
        raise NoChip(str(e)) from None
    cache_dir = use_checkout_cache(jax)
    watch = CompileWatch(jax)
    system.import_program(ROOT)
    tracer = system.tracer()

    daemon = system.start_daemon(devices[:cell["chips"]],
                                 os.path.join(ROOT, ".bench_out", "ckpt"))
    gateway = None
    ld = None
    try:
        serve = next((b for b in blocks if b.kind == "serve"), None)
        if serve is not None:
            gateway = system.start_gateway(daemon, serve.tenant, TOKEN)
        prog: Dict[str, Dict] = {}
        for b in blocks:
            if b.kind == "serve":
                warmed = setup_serve(daemon, b, pseed, gateway)["buckets"]
                log(f"serve set-up warmed {warmed} prefill buckets")
        for b in blocks:
            if b.kind == "train":
                prog[b.tenant] = setup_train(daemon, b, pseed)
        trains = [b for b in blocks if b.kind == "train"]
        if trains:        # one engine-driven step, so the window is steady
            n0 = {b.app: len(daemon.events_since(0, app_id=b.app,
                                                 kinds={"step"}))
                  for b in trains}
            for b in trains:
                wait_step_events(daemon, b.app, n0[b.app] + 1)
        if serve is not None:
            vocab = serve.cfg["vocab_size"]
            reqs = load.serve_requests(serve.mix, vocab, args.seed,
                                       args.seconds, TAIL_S)
            ld = Load(gateway.port, serve.app, reqs)
            rt = daemon.runtime(serve.app)
            base = dict(rt.sessions.stats())

        # ---------------------------------------------------------- window
        trace_dir = os.path.join(ROOT, ".bench_out", "trace",
                                 args.workload)
        spans_seen: Dict[str, object] = {}
        collector_stop = threading.Event()

        def collect():
            while not collector_stop.wait(0.5):
                for s in tracer.spans():
                    spans_seen[s.span_id] = s

        if args.trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracer.enable()
            threading.Thread(target=collect, daemon=True).start()
        w0 = time.perf_counter()
        wall0 = time.time()
        if ld is not None:
            ld.start(w0)
        tw0 = tw1 = w0
        if args.trace:
            span = min(TRACE_S, args.seconds)
            time.sleep(max(0.0, (args.seconds - span) / 2))
            jax.profiler.start_trace(trace_dir)
            ann = jax.profiler.TraceAnnotation(trace_lib.WINDOW)
            ann.__enter__()
            tw0 = time.perf_counter()
            time.sleep(max(0.0, tw0 + span - time.perf_counter()))
            tw1 = time.perf_counter()
            ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            log(f"profiler of {span:g} s stopped in "
                f"{time.perf_counter() - tw1:.1f} s")
        time.sleep(max(0.0, w0 + args.seconds - time.perf_counter()))
        w1 = time.perf_counter()
        wall1 = time.time()
        if args.trace:
            collector_stop.set()
            for s in tracer.spans():
                spans_seen[s.span_id] = s
            tracer.disable()
        setup_s = w0 - T_PROCESS
        compiles_in_window = watch.between(w0, w1)

        # --------------------------------------------- end of the window
        e2e: Dict[str, float] = {"setup_s": setup_s}
        attempted = failed = 0
        steps = {}
        for b in trains:
            rate, n = train_rate(daemon, b, wall0, wall1)
            steps[b.tenant] = n
            attempted += n
            if rate is None:
                failed += 1
            else:
                e2e.setdefault("_train", []).append(rate)
        if "_train" in e2e:
            rates = e2e.pop("_train")
            if len(rates) == len(trains):
                e2e["train_tokens_per_s"] = sum(rates) / len(rates)
        sessions = None
        if ld is not None:
            ld.wait_measured(w1 + DRAIN_S)
            sm = serve_metrics(ld, [m["name"] for m in e2e_decl])
            e2e.update({m["name"]: sm[m["name"]] for m in e2e_decl
                        if TAIL_METRIC.match(m["name"])})
            attempted += sm["n"]
            failed += sm["failed"]
            rt = daemon.runtime(serve.app)
            sessions = {s.session: rt.sessions.sessions.get(s.session)
                        for s in ld.measured if s.session}
            sessions = {k: (v.submitted_t, v.first_token_t)
                        for k, v in sessions.items() if v is not None}
            log(f"serve: {sm['n']} requests due in the window, "
                f"{sm['failed']} failed, {sm['n_gaps']} token gaps; sender "
                f"late by {sm['late_p50_s']} s median, {sm['late_max_s']} s "
                f"at most; scheduler stats before {base} after "
                f"{rt.sessions.stats()}")
            sample = serve_sample(ld, args.seed)
        device = device_summary(jax, devices[:cell["chips"]],
                                [d for b in blocks for d in b.devices])
        log(f"compiles inside the window: {compiles_in_window} "
            f"(XLA compile {watch.compile_s:.1f} s in all, persistent cache "
            f"{cache_dir}: {watch.hits} hits, {watch.misses} misses)")
        log(f"set-up {setup_s:.3f} s; window {w1 - w0:.3f} s (a traced "
            f"run's includes the profiler's stop); steps in the window "
            f"{steps}")

        # ------------------------------------------------------- traced
        per_layer: Dict[str, Dict] = {}
        breakdown = None
        if args.trace:
            spans = [(s.name, s.t0, s.t1, s.args, s.app_id, s.parent_id,
                      s.span_id) for s in spans_seen.values()]
            by_id = {s[6]: s for s in spans}
            t_tr = time.perf_counter()
            tr = trace_lib.load(trace_dir)
            trace_mb = sum(os.path.getsize(os.path.join(d, f)) for d, _, fs
                           in os.walk(trace_dir) for f in fs) / 2 ** 20
            shutil.rmtree(trace_dir, ignore_errors=True)
            to_trace = (trace_lib.clock_map(tr["window"], (tw0, tw1))
                        if tr["window"] else None)
            data = RunData(blocks, peaks, w0, w1, tw0, tw1, spans, by_id, tr,
                           to_trace, ld, sessions)
            lo, hi = data.trace_window() if to_trace else (0.0, 0.0)
            planes = [p for kind in ("train", "serve")
                      for p in data.devices_of(kind)]
            busy = [trace_lib.busy_ns(tr, p, lo, hi) * 1e-9 for p in planes]
            device["busy_s"] = sum(busy) / max(len(busy), 1)
            device["window_s"] = (hi - lo) * 1e-9
            in_win = [(n, a, b) for n, a, b, *_ in spans
                      if b >= tw0 and a <= tw1]
            gaps = [g for p in planes
                    for g in trace_lib.idle_gaps(tr, p, lo, hi)]
            breakdown = {
                "device_ops": trace_lib.top_ops(tr, planes, lo, hi),
                "idle_gaps": trace_lib.attribute_gaps(gaps, in_win,
                                                      to_trace)}
            e2e_names = {m["name"] for m in e2e_decl}
            for m in cell_metrics(bench, args.workload, "per_layer",
                                  e2e_names):
                value = load_reader(bench_dir, m["name"]).read(data)
                if value is not None:
                    per_layer[m["name"]] = {"value": value,
                                            "unit": m["unit"]}
            n_ops = sum(len(d["ops"].code) for d in tr["devices"].values())
            log(f"trace of {device['window_s']:.1f} s ({trace_mb:.0f} MiB, "
                f"{n_ops} device ops) read and reduced in "
                f"{time.perf_counter() - t_tr:.1f} s")

        # ------------------------------------------- free, then check
        if ld is not None:
            ld.close()
        for b in blocks:
            daemon.expire(b.app)
        gateway_, gateway = gateway, None
        if gateway_ is not None:
            gateway_.stop()
        daemon.stop()
        gc.collect()

        # every block against the reference, under its own configuration's
        # limits; in a cell of several blocks each number is named
        # <tenant>.<number>.  Train blocks of one configuration and traffic
        # share the seed, so they share one reference run.
        t_ref = time.perf_counter()
        nums: Dict[str, float] = {}
        limits: Dict[str, float] = {}
        loose: set = set()
        refs: Dict[str, Dict] = {}
        for b in blocks:
            if b.kind == "train":
                key = json.dumps([b.cfg, b.mix], sort_keys=True)
                if key not in refs:
                    with jax.default_device(b.devices[0]):
                        refs[key] = check.train_reference(b.cfg, b.mix,
                                                          pseed)
                ref = refs[key]
                got, notes = check.train_numbers(
                    prog[b.tenant], ref, b.cfg.get("direction_leaf"))
                log(f"train check {b.tenant}: program {prog[b.tenant]['losses']}"
                    f" losses, reference {ref['losses']}; {notes}")
            else:
                with jax.default_device(b.devices[0]):
                    gaps = check.serve_reference(b.cfg, pseed, sample)
                log(f"serve check: {len(sample)} requests, {len(gaps)} "
                    f"served tokens compared")
                got = check.serve_numbers(gaps)
            pre = f"{b.tenant}." if len(blocks) > 1 else ""
            nums.update({pre + k: v for k, v in got.items()})
            limits.update({pre + k: v for k, v in b.cfg["limits"].items()})
            loose.update(pre + k for k in b.cfg.get("not_compared", {}))
        checks, ok = check.judge(nums, limits, loose)
        ok = ok and compiles_in_window == 0 and failed == 0
        log(f"reference and comparison took {time.perf_counter() - t_ref:.1f}"
            f" s")
    finally:
        if ld is not None:
            ld.close()
        if gateway is not None:
            gateway.stop()
        daemon.stop()

    if args.trace:
        metrics = per_layer
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in e2e_decl if m["name"] in e2e}
    result = {"correct": bool(ok), "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


def main() -> int:
    try:
        return run()
    except NoChip as e:
        log(f"bench: no measurement: {e}")
        return 2


if __name__ == "__main__":
    sys.exit(main())
