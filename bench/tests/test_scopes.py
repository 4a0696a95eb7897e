"""``bench/scopes.py`` charges a train step's device time to the layer
scopes that its own step owns.  A scope of ``repro.models.LAYER_SCOPES``
that owns no instruction of the step (another family's layer) reads None,
and the scopes the step owns read as they did before; only a map in which
no scope owns an instruction (stale metadata) is refused whole.

On a made-up step map and a hand-made trace whose answers are known."""
import os
import types

import pytest

import repro.models
from bench import run
from bench import scopes
from bench import trace as T
from repro.obs import programs as P

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
XLSTM = ("mlstm", "slstm", "lm_head", "optimizer")
#: instruction -> (scope, ns it takes in one step run)
XLSTM_OPS = {"fusion.1": ("mlstm", 50.0), "fusion.2": ("slstm", 30.0),
             "dot.3": ("lm_head", 5.0), "subtract.4": ("optimizer", 10.0),
             "copy.5": (P.UNSCOPED, 5.0)}
#: the step of a family with latent attention and experts: no xLSTM layer
MOE_OPS = {"fusion.1": ("mla", 40.0), "fusion.2": ("moe", 70.0),
           "subtract.4": ("optimizer", 10.0), "copy.5": (P.UNSCOPED, 5.0)}


def step_run(ops, monkeypatch, layer_scopes, scope_of=None):
    """RunData of a trace of four runs of the step ``jit_fn`` (the first
    and last cut by the trace), each a box op holding ``ops``, and the step
    map that ``scope_of`` gives (default: each op's own scope)."""
    scope_of = scope_of or (lambda name: ops[name][0])
    smap = P.ScopeMap("jit_fn", {n: scope_of(n) for n in ops})
    monkeypatch.setattr(P.PROGRAMS, "scopes", lambda app: smap)
    monkeypatch.setattr(repro.models, "LAYER_SCOPES", layer_scopes)
    names = ["while.0"] + list(ops)
    code, start, dur, box = [], [], [], []
    runs = [["jit_fn(1)", 1000.0 * k, 200.0] for k in range(4)]
    for _, s0, _ in runs:
        code.append(0), start.append(s0 + 1), dur.append(190.0)
        box.append(True)
        t = s0 + 2
        for i, (_, ns) in enumerate(ops.values(), start=1):
            code.append(i), start.append(t), dur.append(ns), box.append(False)
            t += ns
    trace = {"devices": {"/device:TPU:0": {
        "ops": T.Ops(names, code, start, dur, box), "modules": runs}},
        "window": [0.0, 4000.0]}
    blk = run.Block("tenant-0", "train", {}, {}, app="app-step",
                    devices=(types.SimpleNamespace(id=0),))
    return run.RunData([blk], None, 0.0, 4000.0, 0.0, 4000.0, [], {},
                       trace, lambda t: t)


def per_step_ms(ops):
    return {s: ns * 1e-6 for s, ns in ops.values()}


def read(metric, data):
    return run.load_reader(BENCH, metric).read(data)


#: a reader of a new family's layer, written as the xLSTM readers are
MLA_READER = '''
from bench import scopes


def read(run):
    ms = scopes.layer_ms(run)
    return None if ms is None else ms["mla"]
'''


def test_a_step_that_owns_every_scope_reads_its_time(monkeypatch):
    """The xLSTM step owns every scope of its family: each reads its
    device ms per whole step run, and the readers give those numbers."""
    data = step_run(XLSTM_OPS, monkeypatch, XLSTM)
    assert scopes.layer_ms(data) == pytest.approx(per_step_ms(XLSTM_OPS))
    assert read("mlstm_ms.train", data) == pytest.approx(50e-6)
    assert read("slstm_ms.train", data) == pytest.approx(30e-6)
    assert read("optimizer_ms.train", data) == pytest.approx(10e-6)


def test_scopes_a_new_family_adds_leave_the_xlstm_readings_as_they_are(
        monkeypatch, tmp_path):
    """With a new family's scopes beside the xLSTM ones, the xLSTM step
    reads the scopes it owns exactly as before, and None for the others."""
    with pytest.MonkeyPatch.context() as mp:
        before = scopes.layer_ms(step_run(XLSTM_OPS, mp, XLSTM))
        readers = {m: read(m, step_run(XLSTM_OPS, mp, XLSTM))
                   for m in ("mlstm_ms.train", "slstm_ms.train",
                             "optimizer_ms.train")}
    data = step_run(XLSTM_OPS, monkeypatch, XLSTM + ("mla", "moe"))
    got = scopes.layer_ms(data)
    assert got == dict(before, mla=None, moe=None)
    for m, value in readers.items():
        assert read(m, data) == value
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "mla_ms.train.py").write_text(MLA_READER)
    assert run.load_reader(str(tmp_path), "mla_ms.train").read(data) is None


def test_a_new_family_step_reads_only_its_own_scopes(monkeypatch, tmp_path):
    data = step_run(MOE_OPS, monkeypatch, XLSTM + ("mla", "moe"))
    got = scopes.layer_ms(data)
    assert got == pytest.approx(dict(per_step_ms(MOE_OPS), mlstm=None,
                                     slstm=None, lm_head=None))
    assert read("mlstm_ms.train", data) is None
    assert read("slstm_ms.train", data) is None
    assert read("optimizer_ms.train", data) == pytest.approx(10e-6)
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "mla_ms.train.py").write_text(MLA_READER)
    assert run.load_reader(str(tmp_path), "mla_ms.train").read(data) == \
        pytest.approx(40e-6)


def test_a_map_in_which_no_scope_owns_an_instruction_reads_none(
        monkeypatch):
    """Metadata from a build without the scopes: the whole map is refused,
    and every scope reader reads nothing."""
    data = step_run(XLSTM_OPS, monkeypatch, XLSTM + ("mla",),
                    scope_of=lambda name: P.UNSCOPED)
    assert scopes.layer_ms(data) is None
    for m in ("mlstm_ms.train", "slstm_ms.train", "optimizer_ms.train"):
        assert read(m, data) is None
