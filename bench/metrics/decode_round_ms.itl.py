"""Decode scheduler: mean duration of the ``serve.decode_round`` spans that
lie in the window.  A round holds that round's prefills as well as its
batched decode."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "runtime decode scheduler", \
    "itl_p95_s", "program_span"


def read(run):
    vals = [t1 - t0 for name, t0, t1, *_ in run.spans
            if name == "serve.decode_round" and t0 >= run.w0 and t1 <= run.w1]
    return 1e3 * statistics.fmean(vals) if vals else None
