"""Checkpoint-backed preemption: lifecycle transitions, victim selection,
no-churn guard, waitlist re-entry ahead of the fair-share class, auto-resume
via tick(), bit-identical suspend->resume on the real BlockRuntime, and
resume onto a different chip set / mesh geometry (subprocess, multi-device).
"""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import pytest

from repro.core.block import BlockState
from repro.core.controller import ClusterController
from repro.core.partition import AllocationError, Partitioner
from repro.core.scheduler import SimRuntime
from repro.core.topology import Topology

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def make_ctl(tmp_path, pod_x=4, pod_y=2, n_pods=1):
    topo = Topology(n_pods=n_pods, pod_x=pod_x, pod_y=pod_y)
    dev = jax.devices()[0]
    return ClusterController(topo, devices=[dev] * topo.n_chips,
                             ckpt_root=str(tmp_path / "ckpt"),
                             state_path=str(tmp_path / "state.json"))


def submit_running(ctl, user, n_chips, priority=0, step_s=0.001,
                   ckpt_every=0, pod=None):
    """Admit a block and fake it into RUNNING with a SimRuntime."""
    app_id, grant = ctl.submit(user, f"{user} job", n_chips,
                               priority=priority, pod=pod)
    assert grant is not None, f"{user} did not fit"
    ctl.confirm(app_id, grant.token)
    ctl.registry.set_state(app_id, BlockState.ACTIVE)
    ctl.registry.set_state(app_id, BlockState.RUNNING)
    ctl.runtimes[app_id] = SimRuntime(step_s, ckpt_every=ckpt_every)
    return app_id


# ----------------------------------------------------------- state machine

def test_preempted_transitions(tmp_path):
    ctl = make_ctl(tmp_path)
    a = submit_running(ctl, "alice", 8)
    ctl.preempt(a, "test eviction")
    blk = ctl.registry.get(a)
    assert blk.state == BlockState.PREEMPTED
    assert blk.preempt_count == 1
    assert blk.preemptions[0]["reason"] == "test eviction"
    # PREEMPTED -> RUNNING directly is illegal; resume goes via ACTIVE
    with pytest.raises(ValueError):
        blk.transition(BlockState.RUNNING)
    ctl.resume(a)
    assert blk.state == BlockState.RUNNING


def test_preemption_history_is_persisted(tmp_path):
    ctl = make_ctl(tmp_path)
    a = submit_running(ctl, "alice", 8)
    ctl.runtimes[a].step_count = 7          # unsaved progress
    ctl.preempt(a, "for the test")
    with open(str(tmp_path / "state.json")) as f:
        snap = json.load(f)
    assert snap[a]["state"] == "preempted"
    assert snap[a]["preempt_count"] == 1
    assert snap[a]["preemptions"][0]["progress_lost_steps"] == 7
    assert snap[a]["preemptions"][0]["checkpoint_step"] == 7  # saved on drain


def test_preempted_block_expires_without_resume(tmp_path):
    ctl = make_ctl(tmp_path)
    a = submit_running(ctl, "alice", 8)
    ctl.preempt(a, "evicted")
    ctl.registry.get(a).grant.expires_at = time.time() - 1
    assert ctl.tick() == [a]                # period ends while suspended
    assert ctl.registry.get(a).state == BlockState.EXPIRED
    assert ctl.scheduler.queue_depth() == 0
    assert ctl.partitioner.free_capacity() == 8


# ------------------------------------------------------------- scheduling

def test_high_priority_preempts_running_block(tmp_path):
    ctl = make_ctl(tmp_path)                # 8 chips
    lo = submit_running(ctl, "alice", 8, priority=0)
    hi, grant = ctl.submit("carol", "urgent", 8, priority=5)
    assert grant is not None                # admitted immediately via eviction
    assert ctl.registry.get(lo).state == BlockState.PREEMPTED
    assert ctl.runtimes[lo].suspended
    rep = ctl.monitor.preemption_report()
    assert rep["preempted_total"] == 1
    ctl.partitioner.check_invariants()


def test_victim_selection_ordering(tmp_path):
    """Victim = (lowest priority, least progress since checkpoint, fewest
    chips) among blocks whose chips let the waiter fit."""
    ctl = make_ctl(tmp_path, pod_x=4, pod_y=4)   # 16 chips
    a = submit_running(ctl, "alice", 4, priority=1)
    b = submit_running(ctl, "bob", 4, priority=0)
    c = submit_running(ctl, "carol", 4, priority=0)
    d = submit_running(ctl, "dan", 4, priority=0)
    ctl.runtimes[b].step_count = 9          # bob would lose 9 steps
    ctl.runtimes[c].step_count = 2          # carol would lose 2 -> victim
    ctl.runtimes[d].step_count = 5          # dan would lose 5
    hi, grant = ctl.submit("eve", "urgent", 4, priority=5)
    assert grant is not None
    assert ctl.registry.get(c).state == BlockState.PREEMPTED
    for other in (a, b, d):
        assert ctl.registry.get(other).state == BlockState.RUNNING


def test_no_churn_equal_priority_never_preempts(tmp_path):
    """The no-churn guard: a waiter can only evict *strictly* lower
    priority, so two equal-priority blocks can't displace each other in a
    loop."""
    ctl = make_ctl(tmp_path)
    lo = submit_running(ctl, "alice", 8, priority=3)
    hi, grant = ctl.submit("bob", "same prio", 8, priority=3)
    assert grant is None                    # queued, no eviction
    assert ctl.registry.get(lo).state == BlockState.RUNNING
    assert ctl.registry.get(hi).state == BlockState.QUEUED
    ctl.tick()                              # still no churn on later ticks
    assert ctl.registry.get(lo).state == BlockState.RUNNING
    # and the preempted victim of a real eviction can't re-evict its evictor
    hi2, grant2 = ctl.submit("carol", "urgent", 8, priority=5)
    assert grant2 is not None
    assert ctl.registry.get(lo).state == BlockState.PREEMPTED
    ctl.tick()
    assert ctl.registry.get(hi2).state in (BlockState.APPROVED,)
    assert ctl.registry.get(lo).state == BlockState.PREEMPTED
    assert ctl.monitor.preemption_report()["preempted_total"] == 1


def test_preempted_reenters_ahead_of_fair_share_class(tmp_path):
    """On resume eligibility, an evicted block outranks same-priority
    QUEUED entries regardless of chips its user already holds."""
    ctl = make_ctl(tmp_path)                # 8 chips
    lo = submit_running(ctl, "alice", 8, priority=0)
    # bob queues first (would normally win FIFO + holds 0 chips)
    b, g = ctl.submit("bob", "waiting", 8, priority=0)
    assert g is None
    hi, g2 = ctl.submit("carol", "urgent", 8, priority=5)
    assert g2 is not None                   # evicts alice
    order = [e.app_id for e in ctl.scheduler.ordered_waitlist()]
    assert order == [lo, b]                 # victim ahead of bob
    ctl.expire(hi)                          # capacity frees -> pump
    assert ctl.registry.get(lo).state == BlockState.RUNNING  # resumed first
    assert ctl.registry.get(b).state == BlockState.QUEUED


def test_tick_auto_resumes_when_capacity_frees(tmp_path):
    ctl = make_ctl(tmp_path)
    lo = submit_running(ctl, "alice", 8, priority=0, ckpt_every=2)
    ctl.step_all(rounds=5)
    hi, grant = ctl.submit("carol", "urgent", 8, priority=5)
    assert grant is not None
    steps_at_suspend = ctl.runtimes[lo].step_count
    ctl.registry.get(hi).grant.expires_at = time.time() - 1
    ctl.tick()                              # expire carol + auto-resume alice
    blk = ctl.registry.get(lo)
    assert blk.state == BlockState.RUNNING
    assert ctl.runtimes[lo].step_count == steps_at_suspend
    ctl.step_all(rounds=2)
    assert ctl.runtimes[lo].step_count == steps_at_suspend + 2
    rep = ctl.monitor.preemption_report()
    assert rep["resumed_total"] == 1
    assert rep["mean_resume_wait_s"] >= 0.0


def test_preemption_disabled_keeps_old_behavior(tmp_path):
    ctl = make_ctl(tmp_path)
    ctl.scheduler.preemption_enabled = False
    lo = submit_running(ctl, "alice", 8, priority=0)
    hi, grant = ctl.submit("carol", "urgent", 8, priority=5)
    assert grant is None                    # waits like PR-1 semantics
    assert ctl.registry.get(lo).state == BlockState.RUNNING
    assert ctl.registry.get(hi).state == BlockState.QUEUED


def test_partial_eviction_multi_block(tmp_path):
    """The waiter only needs one victim's rectangle: the smallest
    sufficient lower-priority block is evicted, others keep running."""
    ctl = make_ctl(tmp_path, pod_x=4, pod_y=4)   # 16 chips
    big = submit_running(ctl, "alice", 8, priority=0)
    small = submit_running(ctl, "bob", 8, priority=0)
    ctl.runtimes[big].step_count = 1
    ctl.runtimes[small].step_count = 1
    hi, grant = ctl.submit("carol", "urgent", 4, priority=2)
    assert grant is not None
    preempted = [a for a in (big, small)
                 if ctl.registry.get(a).state == BlockState.PREEMPTED]
    assert len(preempted) == 1              # one victim suffices for 4 chips
    ctl.partitioner.check_invariants()


def test_multi_victim_eviction_when_one_is_not_enough(tmp_path):
    """A waiter whose footprint spans several smaller blocks evicts the
    cheapest sufficient *set* instead of starving until expiry."""
    ctl = make_ctl(tmp_path)                # 8 chips
    a = submit_running(ctl, "alice", 4, priority=0)
    b = submit_running(ctl, "bob", 4, priority=0)
    hi, grant = ctl.submit("carol", "urgent full pod", 8, priority=5)
    assert grant is not None
    assert ctl.registry.get(a).state == BlockState.PREEMPTED
    assert ctl.registry.get(b).state == BlockState.PREEMPTED
    assert ctl.monitor.preemption_report()["preempted_total"] == 2
    ctl.expire(hi)                          # both victims auto-resume
    assert ctl.registry.get(a).state == BlockState.RUNNING
    assert ctl.registry.get(b).state == BlockState.RUNNING
    ctl.partitioner.check_invariants()


def test_no_pointless_eviction_when_set_still_insufficient(tmp_path):
    """If even evicting every eligible block can't fit the waiter, nothing
    is evicted (e.g. part of the pod is held by equal-priority blocks)."""
    ctl = make_ctl(tmp_path)                # 8 chips
    lo = submit_running(ctl, "alice", 4, priority=0)
    peer = submit_running(ctl, "bob", 4, priority=5)   # not evictable
    hi, grant = ctl.submit("carol", "urgent full pod", 8, priority=5)
    assert grant is None
    assert ctl.registry.get(lo).state == BlockState.RUNNING
    assert ctl.registry.get(peer).state == BlockState.RUNNING
    assert ctl.monitor.preemption_report()["preempted_total"] == 0


def test_victim_set_is_pruned_to_contributing_blocks(tmp_path):
    """The greedy multi-victim prefix can pick up a cheap victim whose
    chips don't actually help the waiter fit; pruning must spare it."""
    ctl = make_ctl(tmp_path, pod_x=6, pod_y=2)   # 12 chips
    a = submit_running(ctl, "alice", 4)          # 2x2 at x0
    b = submit_running(ctl, "bob", 2)            # 1x2 at x2
    c = submit_running(ctl, "carol", 4)          # 2x2 at x3; 1x2 free at x5
    ctl.runtimes[a].step_count = 0               # cheapest victim by rank...
    ctl.runtimes[b].step_count = 1
    ctl.runtimes[c].step_count = 2
    hi, grant = ctl.submit("dave", "urgent", 8, priority=5)  # needs 4x2
    assert grant is not None
    # ...but evicting bob+carol alone frees a 4x2 rectangle: alice survives
    assert ctl.registry.get(a).state == BlockState.RUNNING
    assert ctl.registry.get(b).state == BlockState.PREEMPTED
    assert ctl.registry.get(c).state == BlockState.PREEMPTED
    ctl.partitioner.check_invariants()


def test_pod_pinning_survives_preempt_resume(tmp_path):
    """A block pinned to a pod at submission must not silently migrate to
    another pod on auto-resume."""
    ctl = make_ctl(tmp_path, pod_x=2, pod_y=2, n_pods=2)
    a = submit_running(ctl, "alice", 4, pod=0)
    d = submit_running(ctl, "dave", 4, pod=1)
    assert all(c[0] == 0 for c in ctl.registry.get(a).grant.coords)
    hi, g = ctl.submit("carol", "urgent", 4, priority=5, pod=0)
    assert g is not None                        # evicts alice from pod 0
    assert ctl.registry.get(a).state == BlockState.PREEMPTED
    ctl.expire(d)                # pod 1 frees, but alice is pinned to pod 0
    assert ctl.registry.get(a).state == BlockState.PREEMPTED
    ctl.expire(hi)                              # pod 0 frees -> resume there
    blk = ctl.registry.get(a)
    assert blk.state == BlockState.RUNNING
    assert all(c[0] == 0 for c in blk.grant.coords)


def test_resume_returns_to_pre_preemption_state(tmp_path):
    """A victim that was only ACTIVE (job never started) must not come
    back RUNNING after auto-resume."""
    ctl = make_ctl(tmp_path)
    app_id, grant = ctl.submit("alice", "staged", 8)
    ctl.confirm(app_id, grant.token)
    ctl.registry.set_state(app_id, BlockState.ACTIVE)
    ctl.runtimes[app_id] = SimRuntime(0.001)
    ctl.preempt(app_id, "evicted while staged")
    assert ctl.registry.get(app_id).preemptions[-1]["from_state"] == "active"
    ctl.tick()
    assert ctl.registry.get(app_id).state == BlockState.ACTIVE  # not RUNNING


def test_priority_override_is_persisted(tmp_path):
    """submit(priority=N) must stick on the request: victim selection and
    requeue read request.priority, and a mismatch would let an evicted
    lower-priority block bounce its evictor right back out."""
    ctl = make_ctl(tmp_path)
    app_id = ctl.register("alice", "j", 8)       # request.priority == 0
    ctl.scheduler.submit(app_id, priority=7)
    assert ctl.registry.get(app_id).request.priority == 7


def test_preempt_invalid_state_raises_without_mutation(tmp_path):
    """preempt() of a non-running block must fail *before* suspending the
    runtime or releasing chips."""
    ctl = make_ctl(tmp_path)
    a = submit_running(ctl, "alice", 8)
    ctl.registry.set_state(a, BlockState.DONE, "finished")
    held_before = ctl.partitioner.free_capacity()
    with pytest.raises(ValueError, match="cannot preempt"):
        ctl.preempt(a, "too late")
    assert ctl.partitioner.free_capacity() == held_before   # nothing released
    assert not ctl.runtimes[a].suspended
    assert ctl.registry.get(a).state == BlockState.DONE


def test_can_fit_excluding_restores_inventory():
    part = Partitioner(Topology(n_pods=1, pod_x=2, pod_y=2))
    coords = part.allocate(4, "blk_a")
    assert not part.can_fit(2)
    assert part.can_fit_excluding(2, ["blk_a"])
    assert part.can_fit_excluding(4, ["blk_a"])
    assert not part.can_fit_excluding(2, ["blk_other"])
    # dry-run left ownership untouched
    assert all(part.owner_of(c) == "blk_a" for c in coords)
    with pytest.raises(AllocationError):
        part.allocate(2, "blk_b")


# ----------------------------------------------- real-runtime round trips

@pytest.mark.slow
def test_suspend_resume_bit_identical_params(tmp_path):
    """Preempt->resume restores bit-identical state on the real runtime."""
    import numpy as np
    import repro.configs as C
    from repro.core.runtime import JobSpec
    from repro.models.config import ShapeConfig
    from repro.train.optimizer import OptConfig

    ctl = make_ctl(tmp_path, pod_x=2, pod_y=1)
    shape = ShapeConfig("t", "train", seq_len=16, global_batch=2,
                        microbatch=1)
    job = JobSpec(C.get_smoke("xlstm_350m"), shape,
                  opt=OptConfig(warmup_steps=1, total_steps=8))
    a, g = ctl.submit("alice", "train", 1, job=job)
    ctl.step_all(rounds=3)
    rt = ctl.runtimes[a]
    before = [np.asarray(l) for l in jax.tree.leaves(rt.state)]
    steps_before = rt.step_count

    ctl.preempt(a, "bit-identity test")
    assert rt.suspended and rt.state is None
    assert ctl.partitioner.free_capacity() == 2     # chips released
    ctl.tick()                                      # auto-resume
    assert ctl.registry.get(a).state == BlockState.RUNNING
    assert rt.step_count == steps_before
    after = [np.asarray(l) for l in jax.tree.leaves(rt.state)]
    assert len(before) == len(after)
    for x, y in zip(before, after):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()           # bitwise
    ctl.step_all(rounds=1)
    assert rt.step_count == steps_before + 1


@pytest.mark.slow
def test_resume_is_a_compile_cache_hit(tmp_path):
    """Resuming on the same chips must NOT recompile: the rebuilt runtime's
    train step comes out of the compile cache (the first attach was the only
    miss for that signature), and the Monitor counts the hit."""
    import repro.configs as C
    from repro.core.runtime import JobSpec
    from repro.models.config import ShapeConfig
    from repro.train import compile_cache
    from repro.train.optimizer import OptConfig

    compile_cache.GLOBAL.clear()            # process-wide: isolate the test
    ctl = make_ctl(tmp_path, pod_x=2, pod_y=1)
    shape = ShapeConfig("t", "train", seq_len=16, global_batch=2,
                        microbatch=1)
    job = JobSpec(C.get_smoke("xlstm_350m"), shape,
                  opt=OptConfig(warmup_steps=1, total_steps=8))
    a, g = ctl.submit("alice", "train", 1, job=job)
    ctl.step_all(rounds=2)
    first = compile_cache.GLOBAL.stats()
    assert first["misses"] >= 1             # initial attach built the step
    assert first["hits"] == 0

    ctl.preempt(a, "compile-cache test")
    ctl.tick()                              # auto-resume on the same chips
    assert ctl.registry.get(a).state == BlockState.RUNNING
    after = compile_cache.GLOBAL.stats()
    assert after["misses"] == first["misses"], "resume recompiled the step"
    assert after["hits"] >= 1
    ctl.step_all(rounds=1)                  # reused wrapper still steps

    # the bus carried the events and the Monitor translated them
    evs = ctl.bus.events_since(kinds={"compile"})
    actions = [e.payload["action"] for e in evs]
    assert "miss" in actions and "hit" in actions
    rep = ctl.monitor.compile_report()
    assert rep["compile_hits_total"] == after["hits"]
    assert rep["compile_misses_total"] == after["misses"]
    assert rep["compile_hit_rate"] > 0

    # the activation also attached the block's roofline model.  A CPU
    # device has no published peak, so it reports no MFU; with the v5e
    # kind named, the step-time EWMA reads back as achieved-vs-peak
    # utilization
    blk = ctl.registry.get(a)
    assert ctl.monitor.roofline_report()["blocks"][blk.block_id]["mfu"] \
        is None
    assert ctl.monitor.mfu(blk.block_id) is None
    from repro.core.peaks import V5E
    from repro.launch import hlo_analysis
    rt = ctl.runtimes[a]
    ctl.monitor.set_roofline(blk.block_id, hlo_analysis.block_roofline(
        rt.job.cfg, rt.job.shape, len(blk.grant.coords), V5E))
    assert ctl.monitor.mfu(blk.block_id) is not None
    roof = ctl.monitor.roofline_report()
    assert blk.block_id in roof["blocks"] and roof["mean_mfu"] > 0


@pytest.mark.slow
def test_serve_block_suspend_resume_keeps_decode_context(tmp_path):
    """A serve block's KV cache / token / cache_len survive preemption —
    without them a restored decoder would silently restart from an empty
    cache at position 0."""
    import numpy as np
    import repro.configs as C
    from repro.core.runtime import JobSpec
    from repro.models.config import ShapeConfig

    ctl = make_ctl(tmp_path, pod_x=2, pod_y=1)
    shape = ShapeConfig("s", "serve", seq_len=16, global_batch=2,
                        microbatch=1)
    job = JobSpec(C.get_smoke("xlstm_350m"), shape, kind="serve")
    a, g = ctl.submit("alice", "serve", 1, job=job)
    ctl.step_all(rounds=3)                  # decode 3 tokens
    rt = ctl.runtimes[a]
    rt.drain()
    cache_before = [np.asarray(l) for l in jax.tree.leaves(rt.cache)]
    token_before = np.asarray(rt.token)
    len_before = int(rt.cache_len)
    assert len_before == 3

    ctl.preempt(a, "serve context test")
    assert rt.cache is None and rt.token is None
    ctl.tick()                              # auto-resume
    assert ctl.registry.get(a).state == BlockState.RUNNING
    assert int(rt.cache_len) == len_before
    assert np.asarray(rt.token).tobytes() == token_before.tobytes()
    cache_after = [np.asarray(l) for l in jax.tree.leaves(rt.cache)]
    assert len(cache_before) == len(cache_after)
    for x, y in zip(cache_before, cache_after):
        assert x.tobytes() == y.tobytes()
    ctl.step_all(rounds=1)                  # decoding continues
    assert int(rt.cache_len) == len_before + 1


@pytest.mark.slow
def test_resume_on_different_geometry(tmp_path):
    """Suspend on a (2,2) 4-chip mesh, resume on (2,1) 2 chips — the
    checkpoint manager reshards host leaves onto the new mesh; params stay
    bit-identical.  Needs >1 device, so runs in a subprocess."""
    code = f"""
    import jax, numpy as np
    import repro.configs as C
    from repro.core.block import BlockState
    from repro.core.controller import ClusterController
    from repro.core.runtime import JobSpec
    from repro.core.topology import Topology
    from repro.models.config import ShapeConfig
    from repro.train.optimizer import OptConfig

    topo = Topology(n_pods=1, pod_x=4, pod_y=2)
    ctl = ClusterController(topo, ckpt_root={str(tmp_path)!r})
    shape = ShapeConfig("t", "train", seq_len=32, global_batch=4,
                        microbatch=2)
    job = JobSpec(C.get_smoke("xlstm_350m"), shape,
                  opt=OptConfig(warmup_steps=1, total_steps=8))
    a, g = ctl.submit("alice", "train", 4, job=job)
    assert g.mesh_shape == (2, 2), g.mesh_shape
    ctl.step_all(rounds=2)
    rt = ctl.runtimes[a]
    before = [np.asarray(l) for l in jax.tree.leaves(rt.state)]

    ctl.preempt(a, "geometry test")
    grant = ctl.resume(a, n_chips=2)          # resume at half size
    assert grant.mesh_shape in ((1, 2), (2, 1)), grant.mesh_shape
    assert grant.block_id == g.block_id
    assert tuple(rt.mesh.devices.shape) == grant.mesh_shape
    assert rt.step_count == 2
    after = [np.asarray(l) for l in jax.tree.leaves(rt.state)]
    for x, y in zip(before, after):
        assert x.tobytes() == y.tobytes()
    ctl.step_all(rounds=1)
    assert rt.step_count == 3
    ctl.partitioner.check_invariants()
    print("GEOMETRY_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "GEOMETRY_OK" in r.stdout
