"""Observability: tracer span parenting (property test), Prometheus
scrape format, flight-recorder postmortems on pod death, trace-context
survival across preempt/resume, and the gateway's /metrics, /v1/trace,
X-Request-ID and 429/413 surfaces."""
import glob
import json
import os
import re
import subprocess
import sys
import time
import urllib.error
import urllib.request

import jax
import pytest

from _hypothesis_compat import given, settings, st
from repro.core.daemon import ClusterDaemon
from repro.core.runtime import SimJobSpec
from repro.core.topology import Topology
from repro.gateway import GatewayServer, ProfileStore, UserProfile
from repro.obs.flight import RECORDER, FlightRecorder
from repro.obs.metrics import REGISTRY, MetricsRegistry
from repro.obs.trace import TRACER, Tracer

SIM = {"kind": "sim", "step_s": 0.001}


@pytest.fixture(autouse=True)
def _obs_isolation():
    """The tracer/registry/recorder are process-global singletons; reset
    them around every test so traced daemons here don't bleed state into
    (or inherit state from) the rest of the suite."""
    def scrub():
        TRACER.disable()
        TRACER.reset()
        REGISTRY.reset()
        RECORDER.reset()
        RECORDER.dir = None
    scrub()
    yield
    scrub()


def make_daemon(tmp_path, **kw):
    topo = Topology(n_pods=kw.pop("n_pods", 1), pod_x=2, pod_y=1)
    dev = jax.devices()[0]
    return ClusterDaemon(topo, devices=[dev] * topo.n_chips,
                         ckpt_root=str(tmp_path / "ckpt"), **kw)


def req(server, method, path, token=None, body=None, headers=None,
        timeout=15):
    r = urllib.request.Request(server.url + path, method=method,
                               data=(json.dumps(body).encode()
                                     if body is not None else None))
    if token:
        r.add_header("Authorization", f"Bearer {token}")
    for k, v in (headers or {}).items():
        r.add_header(k, v)
    try:
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), resp.headers
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), e.headers


# ==================================================== metrics registry

def test_registry_counters_gauges_hists():
    reg = MetricsRegistry()
    reg.inc("a_total", labels={"k": "x"})
    reg.inc("a_total", 2, labels={"k": "x"})
    reg.inc("a_total", labels={"k": "y"})
    assert reg.counter_value("a_total", labels={"k": "x"}) == 3
    assert reg.counter_total("a_total") == 4
    reg.set_gauge("g", 7)
    assert reg.gauge_value("g") == 7
    for v in (0.001, 0.002, 0.004, 0.1):
        reg.observe("h_seconds", v)
    s = reg.hist_summary("h_seconds")
    assert s["count"] == 4 and abs(s["sum"] - 0.107) < 1e-9
    assert s["min"] <= s["p50"] <= s["p90"] <= s["p99"] <= s["max"]


def test_add_gauge_is_atomic_and_clamps():
    reg = MetricsRegistry()
    assert reg.add_gauge("g", 1) == 1
    assert reg.add_gauge("g", 1) == 2
    assert reg.add_gauge("g", -5) == 0          # clamps, never negative
    assert reg.gauge_value("g") == 0


def test_sample_ring_is_bounded():
    reg = MetricsRegistry()
    for i in range(3 * MetricsRegistry.RING):
        reg.sample("s", i, now=float(i))
    pts = reg.series("s")["s"]
    assert len(pts) == MetricsRegistry.RING
    assert pts[-1] == [float(3 * MetricsRegistry.RING - 1),
                       float(3 * MetricsRegistry.RING - 1)]


# one metric line: name, optional {labels}, numeric value
_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"
    r" -?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?$")


def assert_prometheus_text(text):
    """Every non-comment line must parse as a Prometheus sample."""
    assert text.endswith("\n")
    for line in text.strip().split("\n"):
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            continue
        assert _PROM_LINE.match(line), f"bad scrape line: {line!r}"


def test_render_prometheus_scrape_format():
    reg = MetricsRegistry()
    reg.describe("a_total", "a counter")
    reg.inc("a_total", labels={"user": "alice"})
    reg.set_gauge("g", 1.5)
    reg.observe("h_seconds", 0.01, labels={"name": "tick"})
    text = reg.render()
    assert_prometheus_text(text)
    assert "# HELP a_total a counter" in text
    assert "# TYPE a_total counter" in text
    assert 'a_total{user="alice"} 1' in text
    assert "# TYPE h_seconds summary" in text
    assert 'h_seconds{name="tick",quantile="0.5"}' in text
    assert 'h_seconds_sum{name="tick"}' in text
    assert 'h_seconds_count{name="tick"} 1' in text


# ============================================================= tracer

def test_disabled_tracer_is_inert():
    tr = Tracer()
    sp = tr.span("anything", app_id="app-1")
    assert not sp                               # the shared falsy no-op
    with sp as s:
        s.set(key="ignored")
    tr.record("done", 0.0, 1.0)
    tr.bind("app-1")
    assert tr.spans() == []
    assert tr.context() is None
    assert tr.current_request_id() is None
    assert tr.block_trace("app-1") is None


def check_span_forest(spans):
    """The structural invariants every exported trace must satisfy:
    (1) each parent_id names a span in the set (no dangling edges),
    (2) parent chains terminate at a root (no cycles),
    (3) parent and child agree on the trace id."""
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.parent_id is None:
            continue
        assert s.parent_id in by_id, f"{s.name}: dangling parent"
        assert by_id[s.parent_id].trace_id == s.trace_id
        seen, cur = set(), s
        while cur.parent_id is not None:
            assert cur.span_id not in seen, f"{s.name}: parent cycle"
            seen.add(cur.span_id)
            cur = by_id[cur.parent_id]


@settings(max_examples=20)
@given(st.lists(st.integers(min_value=0, max_value=3),
                min_size=1, max_size=8))
def test_span_parenting_property(depths):
    """Random nesting (same-thread stacks + cross-'thread' ctx handoffs):
    the exported forest always satisfies ``check_span_forest`` and each
    nested child opens within its parent's window."""
    tr = Tracer().enable()
    for d in depths:
        open_spans = [tr.span("root")]
        for i in range(d):
            open_spans.append(tr.span(f"nest{i}"))
        # one cross-thread-style handoff per chain: explicit ctx parent
        ctx = tr.context()
        t0 = time.perf_counter()
        tr.record("queue-wait", t0, time.perf_counter(), ctx=ctx)
        for sp in reversed(open_spans):
            sp.__exit__(None, None, None)
    spans = tr.spans()
    assert len(spans) == sum(d + 2 for d in depths)
    check_span_forest(spans)
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.parent_id is not None:
            assert s.t0 >= by_id[s.parent_id].t0


def test_queue_and_exec_spans_tile_daemon_call(tmp_path):
    """Background daemon: the pump's queue-wait and exec spans for one
    command share the claim timestamp (queue.t1 == exec.t0 exactly) and
    both parent back to the caller's ``daemon.call`` span."""
    d = make_daemon(tmp_path, background=True, tick_interval_s=0.01,
                    trace=True)
    try:
        app, grant = d.submit("alice", "traced", 1)
        assert grant is not None
    finally:
        d.stop()
    spans = {s.name: s for s in TRACER.spans()}
    call = spans["daemon.call:submit"]
    queue = spans["daemon.queue:submit"]
    execs = spans["daemon.exec:submit"]
    assert queue.trace_id == execs.trace_id == call.trace_id
    assert queue.parent_id == call.span_id
    assert execs.parent_id == call.span_id
    assert queue.t1 == execs.t0                 # exact tiling (shared claim)
    assert call.t0 <= queue.t0 and execs.t1 <= call.t1
    check_span_forest(list(TRACER.spans()))


def test_trace_context_survives_preempt_resume(tmp_path):
    """The block binding keys the trace by app_id and outlives the
    runtime object: engine spans recorded after a preempt/resume
    round-trip join the same trace the submit request opened.  (The
    preempt/resume *control* spans correctly belong to their own admin
    requests' traces.)"""
    d = make_daemon(tmp_path, trace=True)
    app, _ = d.submit("alice", "w", 1, job=SimJobSpec(step_s=0.001))
    trace0 = TRACER.block_trace(app)
    assert trace0 is not None
    d.autostep_enable(app)
    d.autostep_round(now=1.0)
    before = [s for s in TRACER.spans(app_id=app) if s.cat == "engine"]
    assert before and all(s.trace_id == trace0 for s in before)

    d.preempt(app, reason="obs test")
    d.resume(app)
    assert TRACER.block_trace(app) == trace0    # binding survived
    d.autostep_enable(app)
    d.autostep_round(now=2.0)
    after = [s for s in TRACER.spans(app_id=app) if s.cat == "engine"]
    assert len(after) > len(before)             # new post-resume spans...
    assert all(s.trace_id == trace0 for s in after)   # ...same trace
    names = {s.name for s in TRACER.spans(app_id=app)}
    assert "ctl.preempt" in names and "ctl.resume" in names
    check_span_forest(list(TRACER.spans()))


# ===================================================== flight recorder

def test_flight_recorder_dump_on_pod_death(tmp_path):
    """Killing a pod writes a postmortem artifact holding the victims'
    final events and spans, publishes a ``postmortem`` event, and the
    artifact file lands under <ckpt_root>/postmortems."""
    d = make_daemon(tmp_path, n_pods=2, trace=True)
    app, _ = d.submit("alice", "victim", 1,
                      job=SimJobSpec(step_s=0.001))
    pod = d.status(app)["pod"]
    victims = d.fail_pod(pod, reason="chaos test")
    assert app in victims
    dumps = RECORDER.dumps()
    assert dumps and dumps[0]["reason"] == "pod_death"
    art = RECORDER.read(dumps[0]["name"])
    assert app in art["apps"]
    assert any(e["app_id"] == app for e in art["events"])
    assert art["per_app_events"][app], "victim's event tail missing"
    assert any(s.get("app_id") == app or s.get("name") == "ctl.preempt"
               for s in art["spans"]), "victim's final spans missing"
    path = dumps[0]["path"]
    assert path and path.startswith(str(tmp_path))
    with open(path) as f:
        on_disk = json.load(f)
    assert on_disk["reason"] == "pod_death"
    assert on_disk["detail"]["pod"] == pod
    # the dump announces itself on the bus and in the counters
    assert any(e.kind == "postmortem" for e in d.events_since(0))
    assert REGISTRY.counter_total("repro_postmortems_total") >= 1


def test_flight_recorder_in_memory_without_dir():
    rec = FlightRecorder(max_events=8)
    meta = rec.dump("unit", apps=None, now=1.0, detail={"x": 1})
    assert meta["path"] is None                 # no dir: in-memory only
    assert rec.last["detail"] == {"x": 1}
    assert rec.read(meta["name"])["reason"] == "unit"
    assert rec.read("nope") is None


# ============================================================ gateway

@pytest.fixture
def gw(tmp_path):
    """Traced background daemon + HTTP gateway (small body cap so the
    413 path is testable with a reasonable payload)."""
    topo = Topology(n_pods=1, pod_x=4, pod_y=2)
    dev = jax.devices()[0]
    daemon = ClusterDaemon(topo, devices=[dev] * topo.n_chips,
                           ckpt_root=str(tmp_path / "ckpt"),
                           background=True, tick_interval_s=0.01,
                           trace=True)
    profiles = ProfileStore([
        UserProfile("alice", "tok-alice", priority=0),
        UserProfile("root", "tok-admin", admin=True),
    ])
    server = GatewayServer(daemon, profiles,
                           max_body_bytes=4096).start()
    yield server, daemon
    server.stop()
    daemon.stop()


def test_metrics_endpoint_scrapes(gw):
    """GET /metrics needs no auth and returns valid Prometheus text
    including the pump-loop and admission-wait histograms."""
    server, daemon = gw
    # a queued admission so the admission-wait histogram has a sample:
    # alice fills the pod, the second submit waits, expiring the first
    # admits it
    s, a, _ = req(server, "POST", "/v1/submit", "tok-alice",
                  {"n_chips": 8, "job": SIM})
    assert s == 201 and a["admitted"]
    s, b, _ = req(server, "POST", "/v1/submit", "tok-alice",
                  {"n_chips": 8, "job": SIM})
    assert s == 201 and not b["admitted"]
    req(server, "POST", f"/v1/blocks/{a['app_id']}/expire", "tok-alice",
        {})
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        s, st, _ = req(server, "GET", f"/v1/blocks/{b['app_id']}",
                       "tok-alice")
        if st["state"] == "running":
            break
        time.sleep(0.02)
    time.sleep(0.05)                  # a few pump ticks for the histogram
    r = urllib.request.urlopen(server.url + "/metrics")   # no auth header
    assert r.status == 200
    assert r.headers["Content-Type"].startswith("text/plain")
    text = r.read().decode()
    assert_prometheus_text(text)
    assert 'repro_pump_tick_seconds{quantile="0.5"}' in text
    assert "repro_admission_wait_seconds_count" in text
    assert "repro_http_requests_total" in text
    assert 'repro_admissions_total{path="queued"}' in text
    # the dashboard's obs report mirrors the same counters
    obs = daemon.obs_report()
    assert obs["trace_enabled"] is True
    assert obs["pump_tick"]["count"] > 0
    assert obs["admission_wait"]["count"] >= 1


def test_request_id_echoed_minted_and_correlated(gw):
    """The gateway echoes a caller's X-Request-ID (minting one when
    absent) and the id rides the trace into event payloads."""
    server, daemon = gw
    before = daemon.bus.latest_seq
    s, a, hdrs = req(server, "POST", "/v1/submit", "tok-alice",
                     {"n_chips": 1, "job": SIM},
                     headers={"X-Request-ID": "req-corr-42"})
    assert s == 201
    assert hdrs["X-Request-ID"] == "req-corr-42"
    evs = [e for e in daemon.events_since(before)
           if e.app_id == a["app_id"]]
    assert evs and all(e.payload.get("request_id") == "req-corr-42"
                       for e in evs if e.kind in ("registered", "admitted"))
    # no header -> one is minted
    _, _, hdrs = req(server, "GET", "/v1/profile", "tok-alice")
    assert hdrs["X-Request-ID"].startswith("req-")


def test_trace_endpoints_chrome_json(gw):
    """/v1/trace (admin) and /v1/blocks/<id>/trace (owner) export valid
    Chrome-trace JSON with a connected span forest: the HTTP request
    span, the daemon queue/exec spans and the scheduler's submit span
    all share the request's trace."""
    server, _ = gw
    s, a, _ = req(server, "POST", "/v1/submit", "tok-alice",
                  {"n_chips": 1, "job": SIM})
    assert s == 201
    app = a["app_id"]
    s, tr, _ = req(server, "GET", "/v1/trace", "tok-admin")
    assert s == 200 and tr["displayTimeUnit"] == "ms"
    for ev in tr["traceEvents"]:
        assert ev["ph"] == "X"
        assert isinstance(ev["ts"], (int, float))
        assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
        assert ev["args"]["trace_id"]
    s, btr, _ = req(server, "GET", f"/v1/blocks/{app}/trace", "tok-alice")
    assert s == 200
    names = {e["name"] for e in btr["traceEvents"]}
    assert any(n.startswith("http.POST:/v1/submit") for n in names)
    assert "daemon.exec:submit" in names
    assert "sched.submit" in names
    traces = {e["args"]["trace_id"] for e in btr["traceEvents"]}
    assert len(traces) == 1                     # one connected trace
    # non-admin cannot read the global trace
    s, _, _ = req(server, "GET", "/v1/trace", "tok-alice")
    assert s == 403


def test_http_413_and_429_counters(gw, tmp_path):
    server, daemon = gw
    big = {"junk": "x" * 8192}                  # > the fixture's 4096 cap
    s, body, _ = req(server, "POST", "/v1/submit", "tok-alice", big)
    assert s == 413 and "exceeds" in body["error"]
    assert REGISTRY.counter_total("repro_http_413_total") >= 1
    # a rate-limited server: burst of 1, negligible refill -> second
    # request trips 429 (shares the daemon; the limiter is per-server)
    limited = GatewayServer(daemon, ProfileStore([
        UserProfile("alice", "tok-limited")]),
        rate_limit_rps=0.001, rate_limit_burst=1).start()
    try:
        s1, _, _ = req(limited, "GET", "/v1/profile", "tok-limited")
        s2, body2, _ = req(limited, "GET", "/v1/profile", "tok-limited")
        assert s1 == 200 and s2 == 429
        assert "retry_after_s" in body2
    finally:
        limited.stop()
    assert REGISTRY.counter_total("repro_http_429_total") >= 1
    rep = daemon.cluster_report()
    assert rep["obs"]["http_413"] >= 1 and rep["obs"]["http_429"] >= 1


def test_postmortem_endpoints(gw):
    server, daemon = gw
    RECORDER.dump("manual", apps=None, now=2.0, detail={"why": "test"})
    s, lst, _ = req(server, "GET", "/v1/postmortems", "tok-admin")
    assert s == 200 and lst["postmortems"]
    name = lst["postmortems"][0]["name"]
    s, art, _ = req(server, "GET", f"/v1/postmortems/{name}", "tok-admin")
    assert s == 200 and art["detail"] == {"why": "test"}
    s, _, _ = req(server, "GET", "/v1/postmortems/nope", "tok-admin")
    assert s == 404
    s, _, _ = req(server, "GET", "/v1/postmortems", "tok-alice")
    assert s == 403                             # admin-only
    # the access log recorded all of the above with latencies
    s, acc, _ = req(server, "GET", "/v1/access?limit=10", "tok-admin")
    assert s == 200 and acc["access"]
    entry = acc["access"][0]
    assert {"t", "method", "path", "status", "ms",
            "request_id"} <= set(entry)


def test_straggler_surfaces_in_status_and_report(tmp_path):
    """A block whose EWMA step time blows past 1.5x its median is
    flagged in ``status()`` and counted in the obs report gauge."""
    d = make_daemon(tmp_path)
    app, _ = d.submit("alice", "slowpoke", 1,
                      job=SimJobSpec(step_s=0.001))
    blk_id = d.registry.get(app).block_id
    mon = d.ctl.monitor
    for _ in range(16):
        mon.record_step(blk_id, 0.01, 1)
    assert d.status(app)["straggler"] is False
    for _ in range(16):                         # EWMA rises, median lags
        mon.record_step(blk_id, 0.1, 1)
    assert d.status(app)["straggler"] is True
    obs = d.obs_report()
    assert blk_id in obs["stragglers"]
    assert REGISTRY.gauge_value("repro_stragglers") == len(
        obs["stragglers"])


# ============================================ spans beside the device ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_prefill_span_nests_in_its_decode_round():
    """``serve.prefill`` surrounds one admission's prefill (bucket, admit
    program, first-token sync) inside ``serve.decode_round``, with the
    session, prompt length and pages as args; off, nothing is recorded."""
    from repro.models import model as model_lib
    from repro.models.config import AttentionConfig, ModelConfig
    from repro.serve.decode_scheduler import DecodeScheduler
    cfg = ModelConfig(name="obs_t", family="dense", n_layers=1, d_model=32,
                      vocab_size=64, d_ff=64,
                      attention=AttentionConfig(n_heads=4, n_kv_heads=2,
                                                head_dim=8),
                      param_dtype="float32")
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0))
    sch = DecodeScheduler(cfg, params, page_size=4, n_pages=0, max_slots=2,
                          max_seq_len=32)
    sch.submit([3, 1, 4, 1, 5], max_new_tokens=3)
    sch.step()                                  # tracer off
    assert TRACER.spans() == []
    sid = sch.submit([2, 7], max_new_tokens=3)
    TRACER.enable()
    sch.step()
    spans = TRACER.spans()
    (pre,) = [s for s in spans if s.name == "serve.prefill"]
    (rnd,) = [s for s in spans if s.name == "serve.decode_round"]
    assert pre.parent_id == rnd.span_id and pre.trace_id == rnd.trace_id
    assert rnd.t0 <= pre.t0 <= pre.t1 <= rnd.t1
    assert pre.args == {"session": sid, "prompt_tokens": 2, "pages": 1}


def test_live_spans_are_annotations_on_the_device_trace_clock(tmp_path):
    """A profile taken while tracing holds every live span as a host
    annotation of the same name, and the benchmark's window map
    (``bench.trace.clock_map``) puts each span's ends within 100 us of its
    annotation's; a recorded span has no annotation."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import trace as bench_trace
    from jax.profiler import ProfileData
    TRACER.enable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(bench_trace.WINDOW):
            h0 = time.perf_counter()
            with TRACER.span("obs.outer"):
                time.sleep(0.02)
                with TRACER.span("obs.inner"):
                    time.sleep(0.01)
                    with TRACER.span("obs.innermost"):
                        time.sleep(0.005)
                time.sleep(0.01)
            TRACER.record("obs.recorded", h0, h0 + 0.001)
            time.sleep(0.005)
            h1 = time.perf_counter()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    events.setdefault(ev.name, []).append(
                        (float(ev.start_ns),
                         float(ev.start_ns + ev.duration_ns)))
    (window,) = events[bench_trace.WINDOW]
    to_trace = bench_trace.clock_map(window, (h0, h1))
    spans = {s.name: s for s in TRACER.spans()}
    for name in ("obs.outer", "obs.inner", "obs.innermost"):
        (a0, a1), = events[name]
        assert abs(to_trace(spans[name].t0) - a0) < 100e3, name
        assert abs(to_trace(spans[name].t1) - a1) < 100e3, name
    assert "obs.recorded" in spans and "obs.recorded" not in events


def test_tracer_runs_without_jax():
    """``repro.obs`` (programs included) imports and traces in a process
    where JAX cannot be imported."""
    code = ("import sys; sys.modules['jax'] = None\n"
            "import repro.obs, repro.obs.programs\n"
            "from repro.obs import TRACER\n"
            "TRACER.enable()\n"
            "with TRACER.span('a'):\n"
            "    pass\n"
            "assert [s.name for s in TRACER.spans()] == ['a']\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
