"""Multi-device integration tests.

These need >1 XLA device, so they run in subprocesses with
``xla_force_host_platform_device_count`` set — unit tests in-process keep
seeing the single real CPU device (dry-run isolation rule).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.mark.slow
def test_controller_full_lifecycle_and_failover(tmp_path):
    """Two tenant blocks run concurrently; chip failure triggers automatic
    re-allocation + checkpoint restore; elastic resize reshards state."""
    out = run_py(f"""
    import jax
    import repro.configs as C
    from repro.core.controller import ClusterController
    from repro.core.runtime import JobSpec
    from repro.core.topology import Topology
    from repro.models.config import ShapeConfig
    from repro.train.optimizer import OptConfig

    topo = Topology(n_pods=1, pod_x=4, pod_y=4)
    ctl = ClusterController(topo, ckpt_root={str(tmp_path)!r})
    shape = ShapeConfig("t", "train", seq_len=32, global_batch=8, microbatch=2)
    a1 = ctl.register("alice", "dense", 4, arch="deepseek_7b")
    a2 = ctl.register("bob", "hybrid", 2, arch="zamba2_2p7b")
    g1 = ctl.review(a1); g2 = ctl.review(a2)
    ctl.partitioner.check_invariants()
    ctl.confirm(a1, g1.token); ctl.confirm(a2, g2.token)
    ctl.activate(a1, JobSpec(C.get_smoke("deepseek_7b"), shape,
                             opt=OptConfig(warmup_steps=2, total_steps=10)))
    ctl.activate(a2, JobSpec(C.get_smoke("zamba2_2p7b"), shape,
                             opt=OptConfig(warmup_steps=2, total_steps=10)))
    ctl.run(a1); ctl.run(a2)
    rep = ctl.interference_report()
    assert rep.isolated, rep.shared_links
    ctl.step_all(rounds=2)
    ctl.runtimes[a1].save(async_=False)
    loss_before = None

    failed = ctl.inject_chip_failure(g1.coords[0])
    assert failed == a1
    assert ctl.registry.get(a1).state.value == "running"
    ctl.step_all(rounds=1)
    st = ctl.runtimes[a1]
    assert st.step_count >= 2   # restored at checkpointed step, stepped once

    ctl.resize_block(a2, 4)
    ctl.step_all(rounds=1)
    assert ctl.registry.get(a2).grant.n_chips == 4
    ctl.partitioner.check_invariants()
    res = ctl.download(a1)
    assert res["checkpoints"], res
    ctl.expire(a1); ctl.expire(a2)
    assert len(ctl.partitioner.free_chips()) == topo.n_chips - 1  # 1 dead
    print("LIFECYCLE_OK")
    """, devices=16)
    assert "LIFECYCLE_OK" in out


@pytest.mark.slow
def test_sharded_equals_single_device_loss():
    """The same train step on a 4-device (2,2) mesh and on a (1,1) mesh gives
    the same loss (sharding does not change semantics)."""
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np
    import repro.configs as C
    from repro.data import pipeline
    from repro.models.config import ShapeConfig
    from repro.sharding import ctx as shard_ctx, plans
    from repro.train import optimizer as opt_lib, train_step as train_lib

    cfg = C.get_smoke("llama4_maverick_400b")   # moe: the interesting case
    shape = ShapeConfig("t", "train", seq_len=32, global_batch=8, microbatch=2)
    opt_cfg = opt_lib.OptConfig(warmup_steps=1, total_steps=4)
    data = pipeline.DataIterator(cfg, shape)
    batch = data.batch(0)

    def run_on(mesh_shape):
        import numpy as np
        devs = np.asarray(jax.devices()[:mesh_shape[0]*mesh_shape[1]])
        mesh = jax.sharding.Mesh(devs.reshape(mesh_shape), ("data","model"))
        axes = plans.MeshAxes(dp=("data",), model="model")
        ctx = shard_ctx.ShardCtx(mesh, ("data",), "model")
        state_abs = train_lib.abstract_train_state(cfg, opt_cfg)
        p_spec = plans.param_specs(state_abs["params"], mesh, axes)
        spec = {"params": p_spec,
                "opt": plans.opt_state_specs(state_abs["opt"], p_spec)}
        sh = plans.to_shardings(spec, mesh)
        step = train_lib.make_train_step(cfg, shape, opt_cfg)
        def fn(state, b):
            with shard_ctx.use(ctx):
                return step(state, b)
        jstep = jax.jit(fn, in_shardings=(sh, None), out_shardings=(sh, None))
        init = jax.jit(lambda k: train_lib.make_train_state(cfg, k, opt_cfg),
                       out_shardings=sh)
        state = init(jax.random.PRNGKey(0))
        losses = []
        for i in range(3):
            state, m = jstep(state, data.batch(i))
            losses.append(float(m["loss"]))
        return losses

    l_multi = run_on((2, 2))
    l_single = run_on((1, 1))
    np.testing.assert_allclose(l_multi, l_single, rtol=2e-2, atol=2e-2)
    print("EQUAL_OK", l_multi, l_single)
    """, devices=8)
    assert "EQUAL_OK" in out


@pytest.mark.slow
def test_overlap_comm_matches_sequential_allreduce():
    """``overlap_comm=True`` (per-microbatch int8 compressed psum over the
    pod axis, folded into the accumulation scan) matches the baseline
    GSPMD fp32 all-reduce within compression tolerance on a real 2-pod
    mesh — params replicated over pod, FSDP/TP over the auto axes."""
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    import repro.configs as C
    from repro.data import pipeline
    from repro.models.config import ShapeConfig
    from repro.sharding import ctx as shard_ctx, plans
    from repro.train import optimizer as opt_lib, train_step as train_lib

    cfg = C.get_smoke("deepseek_7b")
    shape = ShapeConfig("t", "train", seq_len=32, global_batch=8, microbatch=2)
    opt_cfg = opt_lib.OptConfig(warmup_steps=1, total_steps=8)
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    axes = plans.MeshAxes(dp=("data",), model="model")  # pod = replica axis
    ctx = shard_ctx.ShardCtx(mesh, ("data",), "model")
    state_abs = train_lib.abstract_train_state(cfg, opt_cfg)
    p_spec = plans.param_specs(state_abs["params"], mesh, axes)
    spec = {"params": p_spec,
            "opt": plans.opt_state_specs(state_abs["opt"], p_spec)}
    sh = plans.to_shardings(spec, mesh)
    b_sh = NamedSharding(mesh, P(("pod", "data")))
    data = pipeline.DataIterator(cfg, shape)

    def run(**kw):
        step = train_lib.make_train_step(cfg, shape, opt_cfg, **kw)
        def fn(state, b):
            with shard_ctx.use(ctx):
                return step(state, b)
        jstep = jax.jit(fn, in_shardings=(sh, None), out_shardings=(sh, None))
        init = jax.jit(lambda k: train_lib.make_train_state(cfg, k, opt_cfg),
                       out_shardings=sh)
        state = init(jax.random.PRNGKey(0))
        losses = []
        for i in range(3):
            b = jax.tree.map(lambda x: jax.device_put(x, b_sh), data.batch(i))
            state, m = jstep(state, b)
            losses.append(float(m["loss"]))
        return losses

    base = run()
    over = run(overlap_comm=True, mesh=mesh)
    np.testing.assert_allclose(over, base, rtol=0.05, atol=0.05)
    print("OVERLAP_OK", base, over)
    """, devices=8)
    assert "OVERLAP_OK" in out


@pytest.mark.slow
def test_grad_compression_shard_map():
    """int8 compressed cross-pod psum inside partial-auto shard_map matches
    the exact psum within quantization error."""
    out = run_py("""
    import jax, jax.numpy as jnp, numpy as np
    from functools import partial
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.train import grad_compression as gc

    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    g = jax.random.normal(jax.random.PRNGKey(0), (2, 64))  # per-pod grads

    @partial(jax.shard_map, mesh=mesh, in_specs=P("pod"), out_specs=P("pod"),
             axis_names={"pod"})   # manual over pod, GSPMD-auto elsewhere
    def compressed(gp):
        err = jnp.zeros_like(gp)
        red, _ = gc.compressed_psum_pod({"g": gp}, {"g": err}, mesh, "pod")
        return red["g"]

    got = jax.jit(compressed)(g)
    want = jnp.broadcast_to(g.mean(0, keepdims=True), g.shape)
    np.testing.assert_allclose(got, want, atol=0.05)

    red, new_err = gc.compressed_allreduce(
        {"g": g}, {"g": jnp.zeros_like(g)}, mesh, "pod")
    np.testing.assert_allclose(red["g"], want, atol=0.05)
    assert new_err["g"].shape == g.shape
    print("COMPRESS_OK")
    """, devices=8)
    assert "COMPRESS_OK" in out
