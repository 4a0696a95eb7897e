"""The reduction from a profiler trace to busy/idle, kernel time and the
breakdown: on a hand-made trace whose answers are known, and on a small
trace recorded on a TPU v5e (checked in)."""
import os

import pytest

from bench import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace():
    ops = T.Ops(names=["while.7", "fusion.1", "fusion.2",
                       "paged_attention.1", "fusion.3"],
                code=[0, 1, 2, 3, 4],
                start=[95.0, 100.0, 140.0, 300.0, 380.0],
                dur=[90.0, 50.0, 30.0, 40.0, 100.0],
                box=[True, False, False, False, False])
    # the first and last runs of jit_f are cut by the trace's start and
    # stop
    mods = [["jit_f", 0.0, 30.0], ["jit_f", 90.0, 100.0],
            ["jit_f", 290.0, 200.0], ["jit_f", 600.0, 10.0]]
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": mods}},
            "window": [50.0, 450.0]}


def test_busy_is_the_union_of_ops_in_the_window():
    tr = _trace()
    # [95,185) + [300,340) + [380,450) clipped at 450
    assert T.busy_ns(tr, "/device:TPU:0", 50.0, 450.0) == 90 + 40 + 70


def test_idle_gaps():
    gaps = T.idle_gaps(_trace(), "/device:TPU:0", 50.0, 450.0)
    assert gaps == [(50.0, 95.0), (185.0, 300.0), (340.0, 380.0)]


def test_op_names_and_kernel_time():
    text = ("%paged_attention.1 = bf16[4,1,8,128]{3,2,1,0} custom-call("
            "s32[4,8] %tbl.1)")
    assert T.op_name(text) == "paged_attention.1"
    # an op that consumes the kernel's output is not the kernel
    assert T.op_name("%convert_reduce_fusion = f32[] fusion(bf16[4] "
                     "%paged_attention.1)") == "convert_reduce_fusion"
    tr = _trace()
    assert T.kernel_ns(tr, "/device:TPU:0", 0, 1e9, "paged_attention") == \
        (40.0, 1)


def test_module_runs_inside_the_window():
    tr = _trace()
    assert T.module_runs(tr, "/device:TPU:0", 50.0, 450.0, "jit_f") == \
        [("jit_f", 90.0, 100.0)]
    assert T.busiest_program(tr, "/device:TPU:0", 0.0, 1e9) == \
        [["jit_f", 90.0, 100.0], ["jit_f", 290.0, 200.0]]


def test_step_runs_cut_by_the_trace_are_left_out():
    """The train step's program runs as a 12 s trace on a TPU v5e recorded
    them (xlstm_350m, one program a step): the first and last were cut to
    3.628 s and 0.876 s by the trace's start and stop, and the first lies
    inside the window that the harness's annotation marks."""
    mods = [["jit_fn(1)", 106991605.0, 3628159498.0],
            ["jit_fn(1)", 3735155015.0, 3750838293.0],
            ["jit_fn(1)", 7485997144.0, 3750808387.0],
            ["jit_fn(1)", 11236809414.0, 875661227.0]]
    ops = T.Ops([], [], [], [], [])
    tr = {"devices": {"d": {"ops": ops, "modules": mods}}, "window": None}
    runs = T.busiest_program(tr, "d", 100000000.0, 12112020228.0)
    assert [d for _, _, d in runs] == [3750838293.0, 3750808387.0]


def test_top_ops_and_gap_attribution():
    tr = _trace()
    top = T.top_ops(tr, ["/device:TPU:0"], 0, 1e9, n=2)
    assert [k for k, _ in top] == ["fusion.3", "fusion.1"]
    assert [v for _, v in top] == pytest.approx([100e-9, 50e-9])
    to_trace = T.clock_map((50.0, 450.0), (1.0, 1.0 + 400e-9))
    spans = [("host.outer", 1.0, 1.0 + 400e-9),
             ("host.inner", 1.0 + 100e-9, 1.0 + 300e-9)]
    gaps = T.idle_gaps(tr, "/device:TPU:0", 50.0, 450.0)
    got = dict(T.attribute_gaps(gaps, spans, to_trace))
    # gaps [50,95) and [340,380) lie under the outer span only, the gap
    # [185,300) has its midpoint under the inner one
    assert got["host.inner"] == pytest.approx(115e-9)
    assert got["host.outer"] == pytest.approx(85e-9)


def test_recorded_chip_trace():
    """Three calls of one program (a matmul chain under a named scope, then
    the Pallas paged-attention kernel) traced on a TPU v5e."""
    tr = T.read(os.path.join(DATA, "v5e_probe_trace.json.gz"))
    dev = next(p for p in tr["devices"] if p.startswith("/device:TPU"))
    assert tr["window"] is not None
    mods = tr["devices"][dev]["modules"]
    lo = min(s for _, s, _ in mods)
    hi = max(s + d for _, s, d in mods)
    busy = T.busy_ns(tr, dev, lo, hi)
    assert 0 < busy < hi - lo
    ns, n = T.kernel_ns(tr, dev, lo, hi, "paged_attention")
    assert n == 3 and 0 < ns < busy
    # the trace names ops by HLO instruction alone: no named scope in it
    assert T.kernel_ns(tr, dev, lo, hi, "vmem_fused_mlstm") == (0, 0)
    assert len(T.module_runs(tr, dev, lo, hi, "jit_f")) == 3
    # a save and read keep every number
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        T.save(tr, os.path.join(d, "t.json.gz"))
        again = T.read(os.path.join(d, "t.json.gz"))
    assert T.busy_ns(again, dev, lo, hi) == busy
    gaps = T.idle_gaps(tr, dev, lo, hi)
    assert sum(e - s for s, e in gaps) == pytest.approx(hi - lo - busy)
