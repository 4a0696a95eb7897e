"""Autostep engine + event-feed scale-out: inline-mode determinism vs
client-driven stepping, pacing/fairness, run-until termination,
autostep x preemption drain/re-arm, periodic checkpoints, and the
per-block event-ring isolation."""
import time

import jax
import pytest

from repro.core.block import BlockState
from repro.core.daemon import ClusterDaemon
from repro.core.events import EventBus
from repro.core.inflight import InflightWindow
from repro.core.runtime import SimJobSpec
from repro.core.topology import Topology
from repro.engine import BlockView, PacingPolicy


def make_daemon(tmp_path, pod_x=4, pod_y=2, **kw):
    topo = Topology(n_pods=1, pod_x=pod_x, pod_y=pod_y)
    dev = jax.devices()[0]
    return ClusterDaemon(topo, devices=[dev] * topo.n_chips,
                         ckpt_root=str(tmp_path / "ckpt"), **kw)


SIM = SimJobSpec(step_s=0.0005, ckpt_every=2)


def drive_until(daemon, apps, state=BlockState.DONE, timeout=30.0,
                now=None):
    """Inline engine rounds until every app reaches ``state``."""
    deadline = time.monotonic() + timeout
    while not all(daemon.registry.get(a).state == state for a in apps):
        daemon.autostep_round(now=now)
        time.sleep(0.0002)
        assert time.monotonic() < deadline, "autostep never finished"


# ------------------------------------------------------------ determinism

def test_inline_autostep_matches_client_driven_trace(tmp_path):
    """Same workload, two drivers: the engine's step/event stream is
    indistinguishable from client-driven ``run_steps`` — identical step
    counts, identical per-block event payload fields, identical Monitor
    accounting (EWMA from the same model-time now= plumbing)."""
    def workload(d, engine: bool):
        a, g = d.submit("alice", "wl", 4, job=SIM, now=100.0)
        b, g2 = d.submit("bob", "wl", 4, job=SIM, now=100.0)
        assert g is not None and g2 is not None
        if engine:
            d.autostep_enable(a, until_steps=12, now=100.0)
            d.autostep_enable(b, until_steps=12, now=100.0)
            drive_until(d, [a, b], now=101.0)
        else:
            d.run_steps({a: 12, b: 12})
        return a, b

    d1 = make_daemon(tmp_path / "c")
    a1, b1 = workload(d1, engine=False)
    d2 = make_daemon(tmp_path / "e")
    a2, b2 = workload(d2, engine=True)

    for d, a, b in [(d1, a1, b1), (d2, a2, b2)]:
        for app in (a, b):
            assert d.runtime(app).step_count == 12
            bid = d.registry.get(app).block_id
            assert d.monitor.stats[bid].steps == 12
            assert d.monitor.stats[bid].ewma_step_s is not None

    def step_payload_keys(d, app):
        evs = [e for e in d.bus.events_since(0, app_id=app)
               if e.kind == "step"]
        return [sorted(e.payload) for e in evs]

    # the engine publishes the same step payload shape in the same count
    assert step_payload_keys(d1, a1) == step_payload_keys(d2, a2)
    # lifecycle through RUNNING is identical; the engine then adds the
    # run-until DONE transition on top
    def states(d, app):
        return [e.payload["state"]
                for e in d.bus.events_since(0, app_id=app)
                if e.kind == "state"]
    assert states(d1, a1) == ["approved", "confirmed", "active", "running"]
    assert states(d2, a2) == ["approved", "confirmed", "active", "running",
                              "done"]


def test_engine_inert_unless_enabled(tmp_path):
    """No drives -> a round is a no-op and publishes nothing: the
    deterministic mode's event stream is bit-for-bit the pre-engine one
    (what keeps policy_admission.py results unchanged)."""
    d = make_daemon(tmp_path)
    a, _ = d.submit("alice", "plain", 4, job=SIM)
    seq = d.bus.latest_seq
    assert d.autostep_round() == 0
    assert d.autostep_round(now=123.0) == 0
    assert d.bus.latest_seq == seq
    assert not d.engine.armed


# ------------------------------------------------------- pacing / fairness

def test_pacing_policy_weighted_fair_interleave():
    views = [BlockView("hi", priority=4, n_chips=4, room=100),
             BlockView("lo", priority=0, n_chips=4, room=100)]
    plan = PacingPolicy(priority_weight=0.5).allocate(views, budget=30)
    assert len(plan) == 30
    # weight 3.0 vs 1.0 -> ~3:1 split of the slots
    assert 20 <= plan.count("hi") <= 25
    assert plan.count("lo") >= 5
    # a full window (room=0) is structural backpressure: no slots at all
    views = [BlockView("full", priority=9, n_chips=1, room=0),
             BlockView("open", priority=0, n_chips=1, room=8)]
    plan = PacingPolicy().allocate(views, budget=8)
    assert plan == ["open"] * 8


def test_pacing_policy_deadline_boost():
    tight = BlockView("tight", n_chips=4, slack_s=2.0, room=100)
    loose = BlockView("loose", n_chips=4, slack_s=1e6, room=100)
    pol = PacingPolicy(boost_slack_s=30.0, deadline_boost=4.0)
    assert pol.weight(tight) > 2.5 * pol.weight(loose)
    plan = pol.allocate([tight, loose], budget=24)
    assert plan.count("tight") > plan.count("loose")


def test_autostep_rate_cap_on_model_clock(tmp_path):
    """max_rate_hz is enforced by the per-drive token bucket on the same
    clock the rounds run on (model time here: deterministic)."""
    d = make_daemon(tmp_path)
    a, _ = d.submit("alice", "paced", 4, job=SimJobSpec(step_s=0.0))
    d.autostep_enable(a, max_rate_hz=10.0)
    now = 1000.0
    for i in range(200):                     # 2.0 model-seconds of rounds
        d.autostep_round(now=now + i * 0.01)
    # 10 steps/s * 2 s (+ the initial one-token allowance and burst slop)
    steps = d.runtime(a).step_count
    assert 18 <= steps <= 26, steps
    d.autostep_pace(a, None)                 # unpace: free running again
    before = d.runtime(a).step_count
    for i in range(20):
        d.autostep_round(now=now + 10 + i * 0.01)
    assert d.runtime(a).step_count - before > 20
    d.autostep_pace(a, 0.0)                  # rate 0 = pause, not unpaced
    paused_at = d.runtime(a).step_count
    for i in range(20):
        d.autostep_round(now=now + 20 + i * 0.01)
    assert d.runtime(a).step_count <= paused_at + d.scheduler.max_inflight
    assert d.engine.enabled(a)               # still armed, just held


# ----------------------------------------------------- run-until / lifecycle

def test_until_steps_exact_completion_and_done_event(tmp_path):
    d = make_daemon(tmp_path)
    a, _ = d.submit("alice", "count", 4, job=SIM)
    d.autostep_enable(a, until_steps=7)
    drive_until(d, [a])
    assert d.runtime(a).step_count == 7          # never overshoots
    assert d.registry.get(a).state == BlockState.DONE
    evs = d.bus.events_since(0, app_id=a)
    autos = [e for e in evs if e.kind == "autostep"]
    assert [e.payload["action"] for e in autos] == ["enabled", "done"]
    assert autos[-1].payload["steps"] == 7
    assert not d.engine.enabled(a)               # drive retired


def test_until_t_stops_dispatching_but_keeps_block_running(tmp_path):
    d = make_daemon(tmp_path)
    a, _ = d.submit("alice", "timed", 4, job=SimJobSpec(step_s=0.0))
    d.autostep_enable(a, until_t=2000.0)
    for i in range(10):
        d.autostep_round(now=1999.0)
    assert d.runtime(a).step_count > 0
    # past the stop time: the engine harvests the in-flight stragglers,
    # dispatches nothing new, and disarms once the window is empty
    for _ in range(5):
        d.autostep_round(now=2000.5)
    assert not d.engine.enabled(a)               # disarmed, not DONE
    ran = d.runtime(a).step_count
    d.autostep_round(now=2001.0)
    assert d.runtime(a).step_count == ran        # no further dispatches
    assert d.registry.get(a).state == BlockState.RUNNING


def test_autostep_preempt_drains_publishes_and_rearms(tmp_path):
    """Eviction of an engine-driven block: in-flight completions are
    harvested and *published* before the suspend (Monitor loses nothing),
    the drive survives, and the block autosteps again after auto-resume
    to finish its run-until target."""
    d = make_daemon(tmp_path)
    a, g = d.submit("alice", "victim", 8, job=SIM)
    assert g is not None
    d.autostep_enable(a, until_steps=40)
    deadline = time.monotonic() + 20
    while d.runtime(a).step_count < 10:
        d.autostep_round()
        time.sleep(0.0002)
        assert time.monotonic() < deadline
    hi, g2 = d.submit("bob", "urgent", 8, job=SIM, priority=5)
    assert g2 is not None                        # preempted alice
    blk = d.registry.get(a)
    assert blk.state == BlockState.PREEMPTED
    assert d.engine.enabled(a)                   # drive survived
    assert d.runtime(a).inflight_depth == 0      # drained
    bid = blk.block_id
    # every completed step was published before the suspend
    assert d.monitor.stats[bid].steps == d.runtime(a).step_count
    r = d.autostep_round()                       # idles while evicted
    assert d.registry.get(a).state == BlockState.PREEMPTED
    d.expire(hi)
    d.tick()                                     # auto-resume
    assert d.registry.get(a).state == BlockState.RUNNING
    drive_until(d, [a])
    assert d.runtime(a).step_count == 40
    assert d.monitor.stats[bid].steps == 40


def test_autostep_ckpt_interval_saves_periodically(tmp_path):
    """Engine-side periodic checkpoints: a runtime exposing save()/
    last_saved_step gets saved every ckpt_every completions."""
    class FakeRuntime(InflightWindow):
        def __init__(self):
            self.step_count = 0
            self.last_saved_step = 0
            self.saves = []
            self.suspended = False
            self._init_window()

        def _launch(self):
            return None

        def _token_ready(self, token):
            return True

        def _token_wait(self, token):
            pass

        def _completion_record(self, dispatch_t, token):
            self.step_count += 1
            return {"step_s": 0.001}

        def save(self, async_=True):
            self.saves.append(self.step_count)
            self.last_saved_step = self.step_count

    d = make_daemon(tmp_path)
    a, _ = d.submit("alice", "fake", 4, job=SIM)
    rt = FakeRuntime()
    d.ctl.runtimes[a] = rt                       # swap in the probe
    d.autostep_enable(a, until_steps=20, ckpt_every=5)
    drive_until(d, [a])
    assert rt.step_count == 20
    # saves land at interval boundaries as seen per harvest round, so the
    # gap between saves is bounded by ckpt_every + the dispatch window —
    # which bounds progress_lost the same way client-driven saving did
    window = d.scheduler.max_inflight
    assert rt.saves, "no periodic checkpoint under autostep"
    marks = [0] + rt.saves
    gaps = [b - a for a, b in zip(marks, marks[1:])]
    assert all(5 <= g <= 5 + window for g in gaps), rt.saves
    assert rt.saves[-1] >= 20 - (5 + window)


def test_enable_rejects_terminal_and_submit_arms_queued(tmp_path):
    d = make_daemon(tmp_path)
    a, _ = d.submit("alice", "gone", 4, job=SIM)
    d.expire(a)
    with pytest.raises(ValueError):
        d.autostep_enable(a)
    # arming a *queued* block is legal: it steps once admitted
    filler, _ = d.submit("bob", "filler", 8, job=SIM)
    q, g = d.submit("carol", "waits", 8, job=SIM)
    assert g is None
    d.autostep_enable(q, until_steps=5)
    assert d.autostep_round() == 0               # queued: engine idles
    d.expire(filler)                             # frees room; pump admits
    drive_until(d, [q])
    assert d.runtime(q).step_count == 5


# ----------------------------------------------------- event ring isolation

def test_per_block_ring_survives_global_ring_eviction():
    """One hot block's step storm must not evict another block's events:
    per-app queries read the block's own ring."""
    bus = EventBus(history=16, per_block_history=64)
    bus.publish("state", app_id="quiet", state="running")
    first_quiet_seq = bus.latest_seq
    for i in range(200):                          # the storm
        bus.publish("step", app_id="hot", step_s=0.001, n_chips=4)
    bus.publish("state", app_id="quiet", state="done")
    # global ring wrapped long ago: the quiet block's first event is gone
    assert all(e.app_id == "hot" or e.seq > first_quiet_seq
               for e in bus.events_since(0))
    quiet = bus.events_since(0, app_id="quiet")
    assert [e.payload["state"] for e in quiet] == ["running", "done"]
    # the hot block's own ring is bounded, newest-last
    hot = bus.events_since(0, app_id="hot", limit=1000)
    assert len(hot) == 64
    assert hot[-1].seq == bus.latest_seq - 1
    # kind filters and cursors still apply on the per-app path
    assert bus.events_since(0, app_id="quiet", kinds={"state"}) == quiet
    assert bus.events_since(quiet[0].seq, app_id="quiet") == quiet[1:]


# ------------------------------------------------------- failing blocks

def test_block_whose_step_raises_ends_failed_and_waiter_returns(tmp_path):
    """A step that raises (a kernel the compiler refuses, a device OOM)
    fails its own block: the block goes FAILED with the error as its
    reason, a waiter polling for a terminal state returns, the flight
    recorder dumps, and the engine's worker thread keeps driving the
    other tenant's block to DONE."""
    from repro.obs.flight import RECORDER
    d = make_daemon(tmp_path, background=True)
    try:
        bad, _ = d.submit("alice", "boom", 4, job=SIM)
        good, _ = d.submit("bob", "fine", 4, job=SIM)

        def boom():
            raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

        d.runtime(bad).dispatch = boom
        d.autostep_enable(bad, until_steps=5)
        d.autostep_enable(good, until_steps=40)
        terminal = (BlockState.DONE, BlockState.FAILED, BlockState.EXPIRED)
        deadline = time.monotonic() + 30.0
        while d.registry.get(bad).state not in terminal:
            assert time.monotonic() < deadline, "waiter never returned"
            time.sleep(0.01)
        blk = d.registry.get(bad)
        assert blk.state is BlockState.FAILED
        assert "out of HBM" in blk.failure_reason
        assert d.engine.describe(bad) is None        # left the engine
        while not any(bad in m["apps"] for m in RECORDER.dumps()):
            assert time.monotonic() < deadline, "no postmortem dumped"
            time.sleep(0.01)
        while d.registry.get(good).state is not BlockState.DONE:
            assert time.monotonic() < deadline, "other block stalled"
            time.sleep(0.01)
        assert d.runtime(good).step_count == 40
        assert all(t.is_alive() for t in d._pod_workers.values())
    finally:
        d.stop()
