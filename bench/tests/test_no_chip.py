"""Without a TPU, or on a device kind the peaks table does not have, a run
exits non-zero and prints no result line."""
import os
import subprocess
import sys
import types

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_no_tpu_exits_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "xlstm_350m.train_s2048", "--seed", str(2 ** 31 + 5), "--seconds",
         "51", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_unknown_device_kind_is_refused(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v0 test",
                                 id=0)
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    with pytest.raises(run.NoChip, match="no published peaks"):
        run.run(["--workload", "xlstm_350m.train_s2048", "--seed", "1",
                 "--seconds", "51", "--trace", "0"])
