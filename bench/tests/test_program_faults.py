"""Faults of the program that the benchmark has found and the program has
not yet mended, pinned at test size so that a mend shows: each test asserts
what a sound program gives and is a strict expected failure, so it fails
the run (XPASS) once the program is right.  PERF.md, section 7, gives the
readings."""
import pytest

from bench.tests.harness import run_cell


@pytest.mark.xfail(strict=True, reason=(
    "the program's first pre-clip gradient norm at test size reads about "
    "4x the float32 reference's on this seed (PERF.md section 7, item 3)"))
def test_first_gradient_on_seed_2147483689():
    res = run_cell("xlstm_tiny.train", seed=2147483689)
    assert res["correct"] is True, res["checks"]
