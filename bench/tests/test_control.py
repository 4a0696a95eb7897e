"""The control (the reference in float8, put in the program's place) has to
come out as not correct under each cell's limits, at test size; and the
limits of the benchmark's own configurations have to be set."""
import json
import os

import numpy as np

from bench import check, data, load
from bench.tests.harness import CELLS

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    with open(os.path.join(CELLS, "configs", name + ".json")) as f:
        return json.load(f)


def _mix(name):
    with open(os.path.join(CELLS, "traffic", name + ".json")) as f:
        return json.load(f)


def test_train_control_fails_its_limits():
    cfg, mix = _cfg("xlstm_tiny"), _mix("train_tiny")
    ref = check.train_reference(cfg, mix, seed=3)
    ctl = check.train_reference(cfg, mix, seed=3, prec="fp8")
    nums, _ = check.train_numbers(ctl, ref)
    _, ok = check.judge(nums, cfg["limits"])
    assert not ok
    same, _ = check.train_numbers(ref, ref)
    assert check.judge(same, cfg["limits"])[1]


def test_direction_leaf_is_compared():
    """A configuration that names a leaf has that leaf's first-gradient
    direction compared: nought against itself, above it for the control,
    and a run with no limit for it is not correct."""
    cfg, mix = _cfg("xlstm_tiny"), _mix("train_tiny")
    ref = check.train_reference(cfg, mix, seed=4)
    ctl = check.train_reference(cfg, mix, seed=4, prec="fp8")
    same, _ = check.train_numbers(ref, ref, "final_norm/scale")
    nums, _ = check.train_numbers(ctl, ref, "final_norm/scale")
    assert abs(same["leaf_cos"]) < 1e-6
    assert nums["leaf_cos"] > 1e-3
    assert not check.judge(same, {k: 1.0 for k in same
                                  if k != "leaf_cos"})[1]


def test_serve_control_fails_its_limit():
    cfg, mix = _cfg("llama_tiny"), _mix("chat_tiny")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg["vocab_size"], n).tolist()
               for n in (20, 33)]
    # the reference's own greedy tokens stand in for what was served
    from bench.reference import llama
    samples = []
    for p in prompts:
        seq = list(p)
        for _ in range(6):
            lg = llama.logits_at(cfg, 5, np.asarray([seq]),
                                 np.asarray([[len(seq) - 1]]))
            seq.append(int(np.argmax(np.asarray(lg)[0, 0])))
        samples.append((p, seq[len(p):]))
    ref = check.serve_reference(cfg, 5, samples)
    assert float(np.max(ref)) < 1e-5          # its own tokens: no gap
    ctl = check.serve_reference(cfg, 5, samples, prec="fp8")
    _, ok = check.judge(check.serve_numbers(ctl), cfg["limits"])
    assert not ok


def test_benchmark_configs_have_their_limits():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    want = {"train": {"loss", "grad_norm", "grad_leaf", "change_leaf",
                      "grad_cos"},
            "serve": {"logit_gap"}}
    for cell in bench["workloads"]:
        with open(os.path.join(BENCH, "configs",
                               cell["config"] + ".json")) as f:
            cfg = json.load(f)
        with open(os.path.join(BENCH, "traffic",
                               cell["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        if kind in want:
            named = set(cfg["limits"]) | set(cfg.get("not_compared", {}))
            need = want[kind] | ({"leaf_cos"} if "direction_leaf" in cfg
                                 else set())
            assert need <= named, cell["name"]
            assert cfg["limits"], cell["name"]
            # a served model is judged by its logit gap, never only shown
            if kind == "serve":
                assert "logit_gap" in cfg["limits"], cell["name"]


def test_seeds_share_their_work():
    """Every seed offers the same requests at the same times; the seed
    draws only the prompts' token ids."""
    mix = _mix("chat_tiny")
    a = load.serve_requests(mix, 256, 1, 10.0, 5.0)
    b = load.serve_requests(mix, 256, 2 ** 31 + 7, 10.0, 5.0)
    shape = lambda rs: [(len(r.prompt), r.max_new_tokens, r.due_s,
                         r.measured) for r in rs]
    assert shape(a) == shape(b)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert all(r.due_s < 10.0 for r in a if r.measured)
    # the order is the mix's own: another order_seed puts the same lengths
    # and gaps in another order
    c = load.serve_requests(dict(mix, order_seed=1), 256, 1, 10.0, 5.0)
    assert shape(c) != shape(a)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(map(key, a)) == sorted(map(key, c))
    gaps = lambda rs: np.sort(np.diff(
        [r.due_s for r in rs if r.measured] + [10.0]))
    assert np.allclose(gaps(a), gaps(c))


def test_training_rows_all_differ():
    toks, labels = data.lcg_batch(2 ** 31 + 11, 0, 8, 64, 50304)
    assert len({tuple(r) for r in toks}) == 8
    assert (toks[:, 1:] == labels[:, :-1]).all()
