"""The paper's four-tenant host at test size (``host_tiny.mixed``: a serve
block beside three train blocks, one device each, behind one daemon and
gateway), through ``bench/run.py`` on four virtual CPU devices.  The run is
correct, reports what its BENCHMARK.json declares for the cell, and judges
every block under its own configuration's limits: one train block whose
step keeps its state makes the run not correct."""
import glob
import math
import os

from bench import run
from bench.tests.harness import CELLS, run_cell_in_child

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "host_tiny.mixed"
TRAINS = ("train-b", "train-c", "train-d")
TRAIN_NUMBERS = ("loss", "grad_norm", "grad_leaf", "change_leaf", "grad_cos")


def _declared(section):
    bench = run.read_json(os.path.join(CELLS, "BENCHMARK.json"))
    e2e = {m["name"] for m in run.cell_metrics(bench, CELL, "end_to_end", ())}
    if section == "end_to_end":
        return e2e
    return {m["name"] for m in run.cell_metrics(bench, CELL, section, e2e)}


def test_host_cell_is_correct_and_reports_what_it_declares():
    res = run_cell_in_child(CELL, seed=1, devices=4)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["device"]["count"] == 4
    assert set(res["metrics"]) == _declared("end_to_end")
    for name, m in res["metrics"].items():
        assert 0 < m["value"] < math.inf, name
    # every block's numbers, each under its tenant's name and its limit
    want = {"serve-a.logit_gap"} | {f"{t}.{k}" for t in TRAINS
                                    for k in TRAIN_NUMBERS}
    assert set(res["checks"]) == want
    for name, c in res["checks"].items():
        assert c["limit"] is not None and c["value"] <= c["limit"], name


def test_host_cell_traced_reads_its_span_and_counter_readers():
    """The CPU has no device plane, so the device readers give nothing;
    every span and counter reader the cell lists reads a time."""
    res = run_cell_in_child(CELL, seed=2, devices=4, trace=1)
    assert res["correct"] is True
    spans = {"dispatch_ms.train", "front_ms.ttft", "server_ttft_ms.ttft",
             "prefill_ms.ttft", "decode_round_ms.itl"}
    assert spans <= _declared("per_layer")
    for name in spans:
        assert res["metrics"][name]["value"] > 0, name
    assert set(res["metrics"]) <= _declared("per_layer")


def test_one_broken_train_block_makes_the_run_not_correct():
    """The last train block built returns its state unchanged; the other
    two are sound.  The run is not correct, by that block's numbers."""
    res = run_cell_in_child(CELL, seed=1, devices=4,
                            keep_state_of_train_block=2)
    assert res["correct"] is False
    c = res["checks"]["train-d.change_leaf"]
    assert c["value"] > 0.9 > c["limit"]
    for t in TRAINS[:2]:
        for k in TRAIN_NUMBERS:
            c = res["checks"][f"{t}.{k}"]
            assert c["value"] <= c["limit"], (t, k)


def test_every_block_of_a_deployment_has_its_limits():
    """A cell of several blocks is judged by its blocks' own
    configurations: each names the limits a cell of its own is held to;
    and a mixed traffic file names a mix of each kind."""
    deployments = 0
    for path in glob.glob(os.path.join(BENCH, "configs", "*.json")):
        cfg = run.read_json(path)
        if "blocks" not in cfg:
            continue
        deployments += 1
        for b in cfg["blocks"]:
            sub = run.read_json(os.path.join(BENCH, "configs",
                                             b["config"] + ".json"))
            assert sub["limits"], (cfg["name"], b["tenant"])
            assert b["kind"] in ("serve", "train") and b["chips"] == 1
    for path in glob.glob(os.path.join(BENCH, "traffic", "*.json")):
        mix = run.read_json(path)
        if mix["kind"] == "mixed":
            for kind in ("serve", "train"):
                sub = run.read_json(os.path.join(BENCH, "traffic",
                                                 mix[kind] + ".json"))
                assert sub["kind"] == kind, path
    assert deployments
