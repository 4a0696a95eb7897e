"""Train-step layers on the device: the named scopes in the model, the
op -> scope map ``repro.obs.programs`` builds from a compiled step, and the
benchmark's ``*_ms.train`` readers that join a profile's ops to it.

The real step is the tiny xLSTM train step of the benchmark's test cell
(``bench/tests/cells``), built by the runtime as a train block builds it."""
import importlib.util
import json
import os
import re
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run as bench_run                      # noqa: E402
from bench import scopes as bench_scopes                # noqa: E402
from bench import system                                # noqa: E402
from bench import trace as T                            # noqa: E402
from repro.core.block import BlockGrant                 # noqa: E402
from repro.core.runtime import BlockRuntime             # noqa: E402
from repro.models import LAYER_SCOPES                   # noqa: E402
from repro.obs import programs as P                     # noqa: E402

CELLS = os.path.join(ROOT, "bench", "tests", "cells")
READERS = ("slstm", "mlstm", "optimizer")
APP = "app-scopes"


def tiny_job():
    with open(os.path.join(CELLS, "configs", "xlstm_tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(CELLS, "traffic", "train_tiny.json")) as f:
        mix = json.load(f)
    return system.train_job(cfg, mix, 0)


class Compiles:
    """Counts JAX's lowering and backend-compile events while active."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.n = {e: 0 for e in self.EVENTS}
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event in self.n:
            self.n[event] += 1

    def __enter__(self):
        self.n = {e: 0 for e in self.EVENTS}
        self.on = True
        return self

    def __exit__(self, *exc):
        self.on = False
        return False

    @property
    def lowered(self):
        return self.n[self.EVENTS[0]]

    @property
    def compiled(self):
        return self.n[self.EVENTS[1]]


@pytest.fixture(scope="module")
def watch():
    return Compiles()


@pytest.fixture(scope="module")
def built(tmp_path_factory, watch):
    """A train block's runtime (no state, no step run) and the compile
    events its construction caused."""
    grant = BlockGrant.new([(0, 0, 0)], (1, 1), 3600.0)
    with watch:
        rt = BlockRuntime(grant, tiny_job(), [jax.devices()[0]],
                          str(tmp_path_factory.mktemp("ckpt")), app_id=APP)
    yield rt, dict(watch.n)
    rt.release()


@pytest.fixture(scope="module")
def step_map(built, watch):
    """The registered step's scope map (one compile) and its HLO text."""
    rt, _ = built
    with watch:
        smap = P.PROGRAMS.scopes(APP)
    compiles = dict(watch.n)
    fn, args, kw = P.PROGRAMS._steps[APP]
    text = P.compile_fresh(fn, args, kw).as_text()
    return smap, text, compiles


# ------------------------------------------------------- registration

def test_an_untraced_block_registers_references_only(built, step_map):
    """Building the block lowers and compiles nothing for the map; asking
    for the map compiles the step once, and only the first time."""
    _, at_build = built
    assert APP in P.PROGRAMS._steps
    assert at_build == {e: 0 for e in Compiles.EVENTS}
    _, _, at_map = step_map
    assert at_map[Compiles.EVENTS[1]] == 1
    with Compiles() as again:
        assert P.PROGRAMS.scopes(APP) is step_map[0]
    assert again.compiled == 0 and again.lowered == 0


def test_release_forgets_the_step(tmp_path):
    grant = BlockGrant.new([(0, 0, 0)], (1, 1), 3600.0)
    rt = BlockRuntime(grant, tiny_job(), [jax.devices()[0]], str(tmp_path),
                      app_id="app-released")
    assert "app-released" in P.PROGRAMS._steps
    rt.release()
    assert "app-released" not in P.PROGRAMS._steps
    assert P.PROGRAMS.scopes("app-released") is None


# ------------------------------------------------------------ the map

def test_each_layer_scope_owns_instructions(step_map):
    smap, _, _ = step_map
    assert smap.module == "jit_fn"
    owned = {s: sum(1 for v in smap.scope.values() if v == s)
             for s in LAYER_SCOPES + (P.UNSCOPED,)}
    assert all(owned[s] > 0 for s in LAYER_SCOPES), owned


def _computations(text):
    """computation name -> its instruction names, and instruction name ->
    the text after ``=``."""
    comps, insts, comp = {}, {}, None
    for line in text.splitlines():
        if line.endswith("{") and not line[:1].isspace():
            m = P._COMPUTATION.match(line)
            comp = m.group(1) if m else None
            comps.setdefault(comp, [])
            continue
        m = P._INSTRUCTION.match(line)
        if m and comp:
            comps[comp].append(m.group(1))
            insts[m.group(1)] = m.group(2)
    return comps, insts


def test_slstm_scan_body_maps_to_slstm(step_map):
    """Every instruction of the sLSTM recurrence's loop bodies, those with
    no scope of their own included, is sLSTM time."""
    smap, text, _ = step_map
    comps, insts = _computations(text)
    bodies = [re.search(r"body=%([^\s,]+)", rest).group(1)
              for name, rest in insts.items()
              if " while(" in " " + rest.split(", metadata")[0]
              and P.scope_of(P._OP_NAME.search(rest).group(1)
                             if P._OP_NAME.search(rest) else "") == "slstm"]
    assert bodies
    in_bodies = [n for b in bodies for n in comps[b]]
    assert {smap.scope[n] for n in in_bodies} == {"slstm"}
    assert any(P._OP_NAME.search(insts[n]) is None
               or P.scope_of(P._OP_NAME.search(insts[n]).group(1)) is None
               for n in in_bodies)


def test_adamw_ops_map_to_optimizer(step_map):
    """The AdamW update's square roots (the step's only ``sqrt``s: the
    model's norms use ``rsqrt``), at least one per parameter leaf, and
    every instruction traced under the scope, are optimizer time."""
    smap, text, _ = step_map
    _, insts = _computations(text)
    sqrt = [n for n, rest in insts.items() if " sqrt(" in " " + rest]
    n_leaves = len(jax.tree.leaves(P.PROGRAMS._steps[APP][1][0]["params"]))
    assert len(sqrt) >= n_leaves
    assert {smap.scope[n] for n in sqrt} == {"optimizer"}
    opt_named = [n for n, rest in insts.items()
                 if '/optimizer/' in rest.split("op_name=", 1)[-1]]
    assert {smap.scope[n] for n in opt_named} == {"optimizer"}


HLO = """HloModule jit_fn, is_scheduled=true

%fused_computation (param_0.1: f32[4]) -> f32[4] {
  %param_0.1 = f32[4]{0} parameter(0)
  ROOT %tanh.0 = f32[4]{0} tanh(%param_0.1), metadata={op_name="jit(fn)/while/body/transpose(jvp(slstm))/tanh"}
}

%body.1 (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %p = (s32[], f32[4]) parameter(0)
  %copy.5 = f32[4]{0} copy(%p)
  %fusion.2 = f32[4]{0} fusion(%copy.5), kind=kLoop, calls=%fused_computation
  %mul.3 = f32[4]{0} multiply(%fusion.2, %fusion.2), metadata={op_name="jit(fn)/while/body/mul"}
  ROOT %tuple.4 = (s32[], f32[4]) tuple(%p, %mul.3)
}

%cond.1 (p.1: (s32[], f32[4])) -> pred[] {
  %p.1 = (s32[], f32[4]) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main.9 (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="state[\\'layers\\'][\\'slstm\\'][\\'w\\']"}
  %while.7 = (s32[], f32[4]) while(%x), condition=%cond.1, body=%body.1, metadata={op_name="jit(fn)/jvp(mlstm)/slstm/closed_call/while"}
  %dot.8 = f32[4]{0} dot(%x, %x), metadata={op_name="jit(fn)/jvp(lm_head)/dot_general"}
  %add.9 = f32[4]{0} add(%x, %x), metadata={op_name="jit(fn)/checkpoint/rematted_computation/mlstm/vmem_fused_mlstm/add"}
  %copy.10 = f32[4]{0} copy(%x)
  ROOT %sub.11 = f32[4]{0} subtract(%x, %x), metadata={op_name="jit(fn)/optimizer/jit(clip)/sub"}
}
"""


def test_attribution_rules_on_hand_made_hlo():
    smap = P.parse_hlo(HLO)
    assert smap.module == "jit_fn"
    assert smap.scope == {
        "param_0.1": "slstm",      # holder: the fusion in the sLSTM loop
        "tanh.0": "slstm",         # through transpose(jvp(...))
        "p": "slstm", "copy.5": "slstm", "fusion.2": "slstm",
        "mul.3": "slstm", "tuple.4": "slstm",   # the loop body's holder
        "p.1": "slstm", "lt.1": "slstm",
        "x": P.UNSCOPED,           # a parameter path is not a scope
        "while.7": "slstm",        # innermost scope wins
        "dot.8": "lm_head",
        "add.9": "mlstm",          # a kernel's own scope is not a layer
        "copy.10": P.UNSCOPED,
        "sub.11": "optimizer",
    }


# -------------------------------------------------------- the readers

def load_reader(scope):
    path = os.path.join(ROOT, "bench", "metrics", f"{scope}_ms.train.py")
    spec = importlib.util.spec_from_file_location(f"reader_{scope}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def one_of_each(smap):
    """An instruction name for each scope and for ``unscoped``."""
    return {s: next(n for n, v in sorted(smap.scope.items()) if v == s)
            for s in LAYER_SCOPES + (P.UNSCOPED,)}


#: ns each scope's op takes in one step run (the sLSTM op runs twice)
PER_RUN = {"slstm": 2 * 30.0, "mlstm": 50.0, "optimizer": 10.0,
           "lm_head": 5.0, P.UNSCOPED: 5.0}


def hand_trace(names, module="jit_fn", stray=None, per_run=PER_RUN):
    """Four runs of the step (the first and last cut by the trace), each a
    box op holding the scopes' ops (``per_run`` ns a run), plus ops outside
    any whole run; a ``stray`` op name runs inside every run for 20 ns."""
    op_names = ["while.77"] + [names[s] for s in per_run]
    if stray:
        op_names.append(stray)
    code, start, dur, box = [], [], [], []

    def op(i, s, d, b=False):
        code.append(i), start.append(s), dur.append(d), box.append(b)

    runs = [[f"{module}(1)", 1000.0 * k, 200.0] for k in range(4)]
    for _, s0, _ in runs:
        op(0, s0 + 1, 190.0, True)
        t = s0 + 2
        for i, s in enumerate(per_run, start=1):
            for _ in range(2 if s == "slstm" else 1):
                op(i, t, per_run[s] / (2 if s == "slstm" else 1))
                t += 31
        if stray:
            op(len(op_names) - 1, t, 20.0)
        op(1, s0 + 500, 77.0)              # between runs: not the step
    ops = T.Ops(op_names, code, start, dur, box)
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": runs}},
            "window": [0.0, 4000.0]}


def run_data(trace):
    blk = bench_run.Block("tenant-0", "train", {}, {}, app=APP,
                          devices=(jax.devices()[0],))
    to_trace = T.clock_map((0.0, 4000.0), (0.0, 4000.0))
    return bench_run.RunData([blk], None, 0.0, 4000.0, 0.0, 4000.0, [], {},
                             trace, to_trace)


def test_readers_sum_the_step_runs_by_scope(step_map):
    smap, _, _ = step_map
    run = run_data(hand_trace(one_of_each(smap)))
    got = bench_scopes.layer_ms(run)
    # the two whole runs in the middle; per step, ns -> ms
    assert got == pytest.approx({s: v * 1e-6 for s, v in PER_RUN.items()})
    for scope in READERS:
        assert load_reader(scope).read(run) == pytest.approx(
            PER_RUN[scope] * 1e-6)
    leaf = sum(PER_RUN.values()) * 1e-6
    assert sum(got.values()) == pytest.approx(leaf)


def _stale(smap):
    return P.ScopeMap(smap.module, {n: P.UNSCOPED for n in smap.scope})


@pytest.mark.parametrize("scope", READERS)
@pytest.mark.parametrize("fault", ["stale_metadata", "names_not_in_map",
                                   "other_module"])
def test_readers_refuse_a_join_they_cannot_trust(step_map, monkeypatch,
                                                 scope, fault):
    smap, _, _ = step_map
    names = one_of_each(smap)
    if fault == "stale_metadata":
        monkeypatch.setattr(P.PROGRAMS, "scopes", lambda app: _stale(smap))
        trace = hand_trace(names)
    elif fault == "names_not_in_map":
        trace = hand_trace(names, stray="fusion.not_in_the_map")
    else:
        trace = hand_trace(names, module="jit_other")
    assert load_reader(scope).read(run_data(trace)) is None


def test_a_missing_share_under_one_percent_is_reported(step_map):
    """Names the map lacks on less than 1% of the step leave the reading
    standing (and counted in neither scope)."""
    smap, _, _ = step_map
    per_run = {s: v * 100 for s, v in PER_RUN.items()}
    got = bench_scopes.layer_ms(run_data(hand_trace(
        one_of_each(smap), stray="fusion.not_in_the_map", per_run=per_run)))
    assert got == pytest.approx({s: v * 1e-6 for s, v in per_run.items()})


def test_readers_without_a_registered_step():
    run = run_data(hand_trace({s: "x" for s in LAYER_SCOPES + (P.UNSCOPED,)}))
    run.blocks[0].app = "app-never-registered"
    assert all(load_reader(s).read(run) is None for s in READERS)
