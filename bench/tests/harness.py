"""Drive ``bench/run.py`` on the CPU at test size: the look for a chip is
skipped, everything after it runs as on the chip."""
import contextlib
import io
import json
import os

CELLS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "cells")


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
