"""Drive ``bench/run.py`` on the CPU at test size: the look for a chip is
skipped, everything after it runs as on the chip.

A cell of several chips needs as many devices as it has blocks, and the
CPU backend gives a process more than one only where ``XLA_FLAGS`` says
so before JAX starts.  ``run_cell_in_child`` runs such a cell in a process
of its own (``python -m bench.tests.harness``) on that many virtual CPU
devices, optionally with the step of one train block broken underneath."""
import argparse
import contextlib
import io
import json
import os
import subprocess
import sys

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cells")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_cell(workload: str, seed: int, seconds: float = 3.0,
             trace: int = 0):
    from bench import run
    from repro.train import compile_cache
    compile_cache.GLOBAL.clear()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.run(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)],
                     require_tpu=False,
                     bench_path=os.path.join(CELLS, "BENCHMARK.json"),
                     cells_dir=CELLS)
    compile_cache.GLOBAL.clear()
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def run_cell_in_child(workload: str, seed: int, devices: int,
                      trace: int = 0, keep_state_of_train_block=None,
                      timeout: float = 900.0):
    """``run_cell`` in a process with ``devices`` virtual CPU devices.
    ``keep_state_of_train_block=k``: the k-th train block built (0 first)
    gets a step that returns its state unchanged."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([ROOT, os.path.join(ROOT, "src")]),
               XLA_FLAGS=f"{os.environ.get('XLA_FLAGS', '')} "
                         f"--xla_force_host_platform_device_count={devices}")
    cmd = [sys.executable, "-m", "bench.tests.harness", workload, str(seed),
           "--trace", str(trace)]
    if keep_state_of_train_block is not None:
        cmd += ["--keep-state-of-train-block",
                str(keep_state_of_train_block)]
    p = subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                       text=True, timeout=timeout)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def _keep_state_of(k: int) -> None:
    """Break the k-th train step the program builds: it computes the step
    and returns its state unchanged."""
    from repro.train import train_step
    orig = train_step.make_train_step
    built = []

    def make(*a, **kw):
        step = orig(*a, **kw)
        built.append(step)
        if len(built) - 1 != k:
            return step

        def bad(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return bad

    train_step.make_train_step = make


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("seed", type=int)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--keep-state-of-train-block", type=int)
    args = ap.parse_args(argv)
    if args.keep_state_of_train_block is not None:
        _keep_state_of(args.keep_state_of_train_block)
    print(json.dumps(run_cell(args.workload, args.seed, trace=args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
