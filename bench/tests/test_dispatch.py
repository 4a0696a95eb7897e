"""The harness reaches a configuration through its ``family`` and
``reference`` keys alone: a family that no file of the benchmark names,
added as files, is built, checked and counted; every configuration in the
repository resolves its modules; a serve cell's tails are the ones its
metric names ask for."""
import glob
import json
import os
import sys
import types

import numpy as np
import pytest

import bench.families
import bench.reference
from bench import check, families, reference, run, system
from bench.tests.harness import CELLS, run_cell

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOY_FAMILY = '''
def model_config(cfg):
    return ("toy model", cfg["hidden_size"])


def train_step_flops(cfg, B, S):
    return 6.0 * B * S * cfg["hidden_size"] * cfg["vocab_size"]
'''

TOY_REFERENCE = '''
import jax
import jax.numpy as jnp

from bench.reference.common import he, model_key


def init_params(cfg, seed):
    k = model_key(seed)
    return {"embed": he(k, (cfg["vocab_size"], cfg["hidden_size"])),
            "head": he(jax.random.fold_in(k, 1),
                       (cfg["hidden_size"], cfg["vocab_size"]))}


def loss(params, tokens, labels):
    logits = params["embed"][tokens] @ params["head"]
    lp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(lp, labels[..., None], -1))


def loss_and_grad(params, tokens, labels, cfg, prec="f32"):
    return jax.value_and_grad(loss)(params, tokens, labels)


def logits_at(cfg, seed, tokens, idx, prec="f32"):
    params = init_params(cfg, seed)
    h = params["embed"][jnp.take_along_axis(jnp.asarray(tokens),
                                            jnp.asarray(idx), 1)]
    return h @ params["head"]
'''

OPT = {"name": "adamw", "moments": "float32", "lr": 1e-3, "warmup_steps": 1,
       "total_steps": 100, "min_lr_frac": 0.1, "b1": 0.9, "b2": 0.95,
       "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """A configuration of the family ``toy_family``, whose family module
    and reference this test writes into a directory of its own and adds to
    the packages' search paths."""
    fam_dir, ref_dir = tmp_path / "families", tmp_path / "reference"
    fam_dir.mkdir()
    ref_dir.mkdir()
    (fam_dir / "toy_family.py").write_text(TOY_FAMILY)
    (ref_dir / "toy_reference.py").write_text(TOY_REFERENCE)
    monkeypatch.setattr(bench.families, "__path__",
                        [*bench.families.__path__, str(fam_dir)])
    monkeypatch.setattr(bench.reference, "__path__",
                        [*bench.reference.__path__, str(ref_dir)])
    for name in ("bench.families.toy_family",
                 "bench.reference.toy_reference"):
        monkeypatch.delitem(sys.modules, name, raising=False)
    return {"name": "toy", "family": "toy_family",
            "reference": "toy_reference", "hidden_size": 8,
            "vocab_size": 16, "optimizer": OPT, "limits": {}}


def test_new_family_reaches_model_building(toy):
    assert system.model_config(toy) == ("toy model", 8)


def test_unknown_family_is_refused():
    with pytest.raises(ValueError, match="no model for family"):
        system.model_config({"family": "no_such_family"})


def test_new_family_reaches_the_train_reference(toy):
    mix = {"global_batch": 4, "seq_len": 8}
    ref = check.train_reference(toy, mix, seed=2 ** 31 + 3)
    assert len(ref["losses"]) == 3
    assert all(np.isfinite(ref["losses"]))
    assert ref["losses"][2] < ref["losses"][0]
    assert ref["leaf_names"] == ["embed", "head"]
    assert all(c > 0 for c in ref["change_leaf"])
    same, _ = check.train_numbers(ref, ref)
    assert same["grad_leaf"] == 0.0 and same["change_leaf"] == 0.0


def test_new_family_reaches_the_serve_reference(toy):
    samples = [([1, 2, 3], [4, 5])]
    gaps = check.serve_reference(toy, 7, samples)
    assert gaps.shape == (2,) and np.all(gaps >= 0)


def test_new_family_reaches_the_train_count(toy, monkeypatch):
    mfu = run.load_reader(BENCH, "mfu.train")
    step_s = 2.0
    monkeypatch.setattr(mfu.T, "busiest_program",
                        lambda trace, plane, lo, hi: [(0, 0, step_s * 1e9)])
    mix = {"global_batch": 4, "seq_len": 8}
    blk = run.Block("t", "train", toy, mix,
                    devices=(types.SimpleNamespace(id=0),))
    peaks = types.SimpleNamespace(bf16_flops=1e12)
    data = run.RunData([blk], peaks, 0.0, 1.0, 0.0, 1.0, [], {},
                       trace={"devices": {"/device:TPU:0": {}}},
                       to_trace=lambda t: t)
    want = 6.0 * 4 * 8 * 8 * 16
    assert mfu.read(data) == pytest.approx(100 * want / (step_s * 1e12))


def _configs():
    return sorted(glob.glob(os.path.join(BENCH, "configs", "*.json"))
                  + glob.glob(os.path.join(CELLS, "configs", "*.json")))


@pytest.mark.parametrize("path", _configs(),
                         ids=lambda p: os.path.relpath(p, BENCH))
def test_every_configuration_resolves_its_modules(path):
    with open(path) as f:
        cfg = json.load(f)
    if "blocks" in cfg:         # a deployment: its blocks' configurations
        for b in cfg["blocks"]:
            assert os.path.exists(os.path.join(os.path.dirname(path),
                                               b["config"] + ".json"))
        return
    fam, ref = families.of(cfg), reference.of(cfg)
    assert system.model_config(cfg).family == cfg["family"]
    if "optimizer" in cfg:
        assert callable(fam.train_step_flops)
        assert callable(ref.init_params) and callable(ref.loss_and_grad)
    if "serve" in cfg:
        assert callable(fam.decode_token_flops)
        assert callable(fam.paged_attention_calls)
        assert callable(ref.logits_at)


def _load(ttfts, gaps_of_one):
    sent = []
    for i, t in enumerate(ttfts):
        s = run.Sent(types.SimpleNamespace(measured=True), t_due=0.0,
                     t_send=0.0)
        if t is not None:
            s.done = True
            s.times = [t + sum(gaps_of_one[:k]) for k in
                       range(len(gaps_of_one) + 1)]
        sent.append(s)
    return types.SimpleNamespace(measured=sent)


def test_tails_are_the_ones_named():
    ttfts = [0.1 * (i + 1) for i in range(10)]
    ld = _load(ttfts, [0.01, 0.03])
    sm = run.serve_metrics(ld, ["ttft_p80_s", "ttft_p90_s", "itl_p95_s",
                                "setup_s"])
    assert sm["ttft_p80_s"] == pytest.approx(run.quantile(ttfts, 0.8))
    assert sm["ttft_p90_s"] == pytest.approx(run.quantile(ttfts, 0.9))
    assert sm["itl_p95_s"] == pytest.approx(0.03)
    assert "setup_s" not in sm
    # a request that never finished is an infinite time to first token
    sm = run.serve_metrics(_load(ttfts[:-1] + [None], [0.01]),
                           ["ttft_p90_s"])
    assert sm["failed"] == 1 and sm["ttft_p90_s"] == float("inf")


def test_serve_cell_reports_its_tails_and_span_readers():
    """At test size on the CPU: the untraced run reports the tails its
    BENCHMARK.json declares; the traced run reads every span and counter
    reader of the serve cell (the CPU has no device plane, so the device
    readers give nothing)."""
    res = run_cell("llama_tiny.chat", seed=2 ** 31 + 21)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"ttft_p80_s", "itl_p95_s", "setup_s"}
    res = run_cell("llama_tiny.chat", seed=2 ** 31 + 22, trace=1)
    assert res["correct"] is True
    for name in ("front_ms.ttft", "server_ttft_ms.ttft", "prefill_ms.ttft",
                 "decode_round_ms.itl"):
        assert res["metrics"][name]["value"] > 0, name
