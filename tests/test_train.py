"""Optimizer, quantized state, grad compression, checkpoint, and an
end-to-end loss-goes-down integration test with checkpoint-resume
equivalence."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import repro.configs as C
from repro.checkpoint.manager import CheckpointManager
from repro.data import pipeline
from repro.models.config import ShapeConfig
from repro.train import grad_compression as gc
from repro.train import optimizer as opt_lib
from repro.train import quantized_state as qs
from repro.train import train_step as train_lib

KEY = jax.random.PRNGKey(3)


# ----------------------------------------------------------------- adamw

def test_adamw_converges_quadratic():
    cfg = opt_lib.OptConfig(lr=0.1, warmup_steps=0, total_steps=200,
                            weight_decay=0.0, min_lr_frac=1.0)
    target = jnp.array([1.5, -2.0, 0.5])
    params = {"w": jnp.zeros(3)}
    state = opt_lib.init(params, cfg)
    for _ in range(150):
        grads = {"w": 2 * (params["w"] - target)}
        params, state, _ = opt_lib.apply(cfg, params, state, grads)
    np.testing.assert_allclose(params["w"], target, atol=0.05)


def test_adamw_clipping():
    cfg = opt_lib.OptConfig(clip_norm=1.0, warmup_steps=0)
    params = {"w": jnp.zeros(4)}
    state = opt_lib.init(params, cfg)
    huge = {"w": jnp.full(4, 1e6)}
    _, _, metrics = opt_lib.apply(cfg, params, state, huge)
    assert float(metrics["grad_norm"]) > 1e6  # reported unclipped


def test_schedule_warmup_cosine():
    cfg = opt_lib.OptConfig(lr=1.0, warmup_steps=10, total_steps=100,
                            min_lr_frac=0.1)
    assert float(opt_lib.schedule(cfg, jnp.int32(5))) == pytest.approx(0.5)
    assert float(opt_lib.schedule(cfg, jnp.int32(10))) == pytest.approx(1.0)
    assert float(opt_lib.schedule(cfg, jnp.int32(100))) == pytest.approx(0.1)


def test_adamw_int8_states_track_fp32():
    """8-bit Adam should land near the fp32 trajectory on a toy problem."""
    target = jnp.array([1.5, -2.0, 0.5, 3.0] * 64)   # 256 elems = 1 block
    def run(bits):
        cfg = opt_lib.OptConfig(lr=0.05, warmup_steps=0, total_steps=300,
                                weight_decay=0.0, min_lr_frac=1.0,
                                state_bits=bits)
        params = {"w": jnp.zeros_like(target)}
        state = opt_lib.init(params, cfg)
        for _ in range(200):
            grads = {"w": 2 * (params["w"] - target)}
            params, state, _ = opt_lib.apply(cfg, params, state, grads)
        return params["w"]
    w8, w32 = run(8), run(None)
    np.testing.assert_allclose(w8, target, atol=0.15)
    np.testing.assert_allclose(w8, w32, atol=0.15)


def test_adamw_fused_dispatch_matches_reference():
    """``fused="jnp"`` replays ``_adam_leaf`` literally, so ``apply`` must be
    bitwise identical to the composed ``fused="off"`` reference for both
    state formats — across the scan_stacked layer-slice path, a ragged
    matrix, a 1-D vector, and a scalar leaf — over two steps so the
    requantized state feeds back through the dispatcher."""
    ks = jax.random.split(KEY, 8)
    params = {
        "stack": jax.random.normal(ks[0], (4, 256, 256)).astype(jnp.bfloat16),
        "w": jax.random.normal(ks[1], (8, 300)).astype(jnp.bfloat16),
        "b": jax.random.normal(ks[2], (257,), jnp.float32),
        "t": jnp.float32(0.3),
    }
    grads = {k: jax.random.normal(kk, p.shape, jnp.float32)
             for (k, p), kk in zip(sorted(params.items()), ks[4:])}

    def run(fused, bits):
        cfg = opt_lib.OptConfig(state_bits=bits, fused=fused)
        state = opt_lib.init(params, cfg)
        p2, s2, _ = opt_lib.apply(cfg, params, state, grads)
        return opt_lib.apply(cfg, p2, s2, grads)[:2]

    for bits in (None, 8):
        ref, out = run("off", bits), run("jnp", bits)
        eq = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)), ref, out)
        assert all(jax.tree.leaves(eq)), (bits, eq)


# ------------------------------------------------------- quantized state

@given(st.integers(1, 900), st.floats(0.01, 100.0))
@settings(max_examples=30, deadline=None)
def test_quantize_roundtrip_error_bound(n, scale):
    """Property: blockwise int8 roundtrip error <= blockmax/127."""
    x = jnp.sin(jnp.arange(n, dtype=jnp.float32) * 0.7) * scale
    q = qs.quantize(x)
    back = qs.dequantize(q)
    assert back.shape == x.shape
    err = np.max(np.abs(np.asarray(back - x)))
    assert err <= scale / 127.0 * 1.01 + 1e-7


def test_quantize_multidim():
    x = jax.random.normal(KEY, (3, 5, 300))
    back = qs.dequantize(qs.quantize(x))
    assert back.shape == x.shape
    assert np.max(np.abs(np.asarray(back - x))) < np.max(np.abs(x)) / 100


def test_quantize_scalar_leaf():
    x = jnp.float32(0.37)
    st_ = qs.quantize(x)
    assert st_["q"].shape == () and st_["s"].shape == (1,)
    back = qs.dequantize(st_)
    assert back.shape == ()
    assert abs(float(back) - 0.37) <= 0.37 / 127 * 1.01


def test_quantize_zero_tensor():
    x = jnp.zeros((3, 700))
    st_ = qs.quantize(x)
    assert np.all(np.asarray(st_["s"]) == 1.0)   # amax=0 -> scale 1, not 0/0
    assert np.all(np.asarray(st_["q"]) == 0)
    assert jnp.array_equal(qs.dequantize(st_), x)


def test_zeros_like_quantized_shapes():
    for shape in [(), (5,), (300,), (2, 3, 513)]:
        p = jnp.zeros(shape, jnp.bfloat16)
        st_ = qs.zeros_like_quantized(p)
        assert st_["q"].shape == shape
        nb = -(-(shape[-1] if shape else 1) // qs.BLOCK)
        assert st_["s"].shape == ((*shape[:-1], nb) if shape else (nb,))
        assert jnp.array_equal(qs.dequantize(st_), jnp.zeros(shape))


def test_pad_to_block_edges():
    x, pad = qs._pad_to_block(jnp.ones((2, 256)))
    assert pad == 0 and x.shape == (2, 256)
    x, pad = qs._pad_to_block(jnp.ones((2, 257)))
    assert pad == 255 and x.shape == (2, 512)
    assert float(x[0, 257]) == 0.0   # zero fill
    x, pad = qs._pad_to_block(jnp.ones((1,)))
    assert pad == 255 and x.shape == (256,)


# -------------------------------------------------------- grad compression

def test_compression_error_feedback_property():
    """EF property: sum of (quantized + carried error) over steps converges
    to the true gradient sum (error does not accumulate unboundedly)."""
    rng = np.random.default_rng(0)
    g_true = jnp.asarray(rng.normal(size=257).astype(np.float32))
    err = jnp.zeros_like(g_true)
    total_q = jnp.zeros_like(g_true)
    for _ in range(50):
        codes, scale, err = gc.compress_residual(g_true, err)
        total_q = total_q + gc.dequantize(codes, scale)
    np.testing.assert_allclose(total_q / 50, g_true,
                               atol=float(jnp.abs(g_true).max()) / 100)


def test_quantize_exact_for_uniform():
    g = jnp.full((128,), 0.5)
    codes, scale = gc.quantize(g)
    np.testing.assert_allclose(gc.dequantize(codes, scale), g, rtol=1e-6)


# ------------------------------------------------------------- checkpoint

def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), namespace="t")
    tree = {"a": jnp.arange(8, dtype=jnp.bfloat16),
            "b": {"c": jnp.ones((3, 3)), "d": jnp.int32(7)},
            "count": 5}
    mgr.save(3, tree)
    restored, step = mgr.restore(tree)
    assert step == 3
    np.testing.assert_array_equal(np.asarray(restored["a"], np.float32),
                                  np.asarray(tree["a"], np.float32))
    assert restored["count"] == 5
    assert restored["a"].dtype == jnp.bfloat16


def test_checkpoint_gc_and_latest(tmp_path):
    mgr = CheckpointManager(str(tmp_path), namespace="t", keep=2)
    tree = {"x": jnp.zeros(4)}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    assert mgr.steps() == [3, 4]
    assert mgr.latest_step() == 4


def test_checkpoint_corruption_detected(tmp_path):
    mgr = CheckpointManager(str(tmp_path), namespace="t")
    tree = {"x": jnp.arange(100, dtype=jnp.float32)}
    path = mgr.save(1, tree)
    leaf = os.path.join(path, "leaf_00000.npy")
    arr = np.load(leaf)
    arr[0] = 999.0
    np.save(leaf, arr)
    with pytest.raises(IOError, match="crc"):
        mgr.restore(tree)


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), namespace="t")
    tree = {"x": jnp.ones((64, 64))}
    mgr.save_async(1, tree)
    mgr.wait()
    restored, _ = mgr.restore(tree)
    np.testing.assert_array_equal(restored["x"], tree["x"])


def test_accum_dtype_policy():
    big = jnp.zeros((2048, 2048))        # 4M elements: at the threshold
    small = jnp.zeros((256, 256))
    assert train_lib.accum_dtype("mixed", big) == jnp.bfloat16
    assert train_lib.accum_dtype("mixed", small) == jnp.float32
    assert train_lib.accum_dtype("f32", big) == jnp.float32
    assert train_lib.accum_dtype("mixed", small, threshold=0) == jnp.bfloat16


def test_train_step_mixed_accum_close_to_f32():
    """``accum="mixed"`` with the threshold forced to 0 (every leaf
    accumulates in bf16) must track the fp32-accumulator loss trajectory
    within bf16 accumulation error, while actually perturbing the params
    (proof the bf16 path ran)."""
    cfg = C.get_smoke("deepseek_7b")
    shape = ShapeConfig("t", "train", seq_len=64, global_batch=4,
                        microbatch=2)
    opt_cfg = opt_lib.OptConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    data = pipeline.DataIterator(cfg, shape)

    def run(**kw):
        step = jax.jit(train_lib.make_train_step(cfg, shape, opt_cfg, **kw))
        state = train_lib.make_train_state(cfg, KEY, opt_cfg)
        losses = []
        for i in range(6):
            state, m = step(state, data.batch(i))
            losses.append(float(m["loss"]))
        return losses, state

    l_f32, s_f32 = run()
    l_mix, s_mix = run(accum="mixed", accum_threshold=0)
    np.testing.assert_allclose(l_mix, l_f32, rtol=0.02, atol=0.02)
    leaves_f32 = jax.tree.leaves(s_f32["params"])
    leaves_mix = jax.tree.leaves(s_mix["params"])
    assert any(not bool(jnp.array_equal(a, b))
               for a, b in zip(leaves_f32, leaves_mix)), \
        "bf16 accumulation produced bitwise-identical params — path not taken?"
    # default threshold: no smoke-model leaf reaches 4M elems, so "mixed"
    # must be bitwise identical to "f32"
    l_mix_def, s_def = run(accum="mixed")
    assert l_mix_def == l_f32
    eq = jax.tree.map(lambda a, b: bool(jnp.array_equal(a, b)),
                      s_f32["params"], s_def["params"])
    assert all(jax.tree.leaves(eq))


def test_train_step_overlap_comm_matches_serial_single_pod():
    """``overlap_comm`` on a 1-pod mesh degenerates to per-microbatch int8
    quantization with error feedback — the loss trajectory must track the
    serial path within compression tolerance.  (Real multi-pod reduction is
    covered in test_multidevice.py.)"""
    cfg = C.get_smoke("deepseek_7b")
    shape = ShapeConfig("t", "train", seq_len=64, global_batch=4,
                        microbatch=2)
    opt_cfg = opt_lib.OptConfig(lr=1e-3, warmup_steps=2, total_steps=20)
    data = pipeline.DataIterator(cfg, shape)
    mesh = jax.make_mesh((1,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))

    def run(**kw):
        step = jax.jit(train_lib.make_train_step(cfg, shape, opt_cfg, **kw))
        state = train_lib.make_train_state(cfg, KEY, opt_cfg)
        losses = []
        for i in range(6):
            state, m = step(state, data.batch(i))
            losses.append(float(m["loss"]))
        return losses

    base = run()
    over = run(overlap_comm=True, mesh=mesh)
    np.testing.assert_allclose(over, base, rtol=0.05, atol=0.05)


def test_train_step_overlap_comm_requires_pod_axis():
    cfg = C.get_smoke("deepseek_7b")
    shape = ShapeConfig("t", "train", seq_len=32, global_batch=4,
                        microbatch=2)
    with pytest.raises(AssertionError):
        train_lib.make_train_step(cfg, shape, opt_lib.OptConfig(),
                                  overlap_comm=True, mesh=None)


# --------------------------------------------------------- compile cache

def test_compile_cache_freeze_is_hashable_and_order_insensitive():
    from repro.train import compile_cache as cc
    cfg = opt_lib.OptConfig()
    k = cc.freeze(cfg)
    hash(k)                                         # usable as a dict key
    assert k[0] == "OptConfig"
    assert cc.freeze({"b": 2, "a": [1, {2}]}) == \
        cc.freeze({"a": (1, frozenset({2})), "b": 2})
    assert cc.mesh_fingerprint(None) == ("default",)
    mesh = jax.make_mesh((1,), ("pod",))
    fp = cc.mesh_fingerprint(mesh)
    assert fp[0] == (("pod", 1),) and len(fp[1]) == 1
    assert fp == cc.mesh_fingerprint(jax.make_mesh((1,), ("pod",)))


def test_persistent_cache_dir_env_wins_else_fixed_checkout_path(
        monkeypatch):
    """Entry points keep JAX's persistent compile cache where
    JAX_COMPILATION_CACHE_DIR says, setting nothing; without it, at one
    fixed path inside the checkout (the path is part of the cache key)."""
    from repro.train import compile_cache as cc
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert cc.use_persistent_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert cc.use_persistent_cache() == cc.PERSISTENT_DIR
        assert jax.config.jax_compilation_cache_dir == cc.PERSISTENT_DIR
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert cc.PERSISTENT_DIR == os.path.join(root, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_hit_miss_and_events():
    from repro.core.events import EventBus
    from repro.train import compile_cache as cc

    cache = cc.CompileCache()
    bus = EventBus()
    cache.set_bus(bus)
    builds = []

    def builder():
        builds.append(1)
        return "artifact"

    assert cache.get(("k", 1), builder, label="unit") == "artifact"
    assert cache.get(("k", 1), builder, label="unit") == "artifact"
    assert cache.get(("k", 2), builder) == "artifact"
    assert builds == [1, 1]                         # second call was a hit
    assert cache.stats() == {"hits": 1, "misses": 2, "entries": 2}
    actions = [e.payload["action"]
               for e in bus.events_since(kinds={"compile"})]
    assert actions == ["miss", "hit", "miss"]
    cache.clear()
    assert cache.stats() == {"hits": 0, "misses": 0, "entries": 0}


# ----------------------------------------------------------- integration

@pytest.mark.slow
def test_training_reduces_loss_and_resumes(tmp_path):
    """30 steps of a tiny xlstm: loss decreases; stopping at 15 and resuming
    from checkpoint reproduces the same final loss (bitwise state restore)."""
    cfg = C.get_smoke("deepseek_7b")
    shape = ShapeConfig("t", "train", seq_len=64, global_batch=4,
                        microbatch=2)
    opt_cfg = opt_lib.OptConfig(lr=1e-3, warmup_steps=2, total_steps=30)
    step = jax.jit(train_lib.make_train_step(cfg, shape, opt_cfg))
    data = pipeline.DataIterator(cfg, shape)

    state = train_lib.make_train_state(cfg, KEY, opt_cfg)
    losses = []
    mgr = CheckpointManager(str(tmp_path), namespace="run")
    for i in range(30):
        state, m = step(state, data.batch(i))
        losses.append(float(m["loss"]))
        if i == 14:
            mgr.save(15, state)
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, losses

    # resume path
    state2 = train_lib.make_train_state(cfg, KEY, opt_cfg)
    state2, _ = mgr.restore(state2)
    losses2 = []
    for i in range(15, 30):
        state2, m = step(state2, data.batch(i))
        losses2.append(float(m["loss"]))
    np.testing.assert_allclose(losses2, losses[15:], rtol=1e-4)
