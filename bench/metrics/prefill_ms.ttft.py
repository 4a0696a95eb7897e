"""Decode scheduler: mean duration of the ``serve.prefill`` spans that lie
in the window: one admitted request's batch-1 prefill, its pages written
and its first token read back."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "runtime decode scheduler", \
    "ttft_p80_s", "program_span"


def read(run):
    vals = [t1 - t0 for name, t0, t1, *_ in run.spans
            if name == "serve.prefill" and t0 >= run.w0 and t1 <= run.w1]
    return 1e3 * statistics.fmean(vals) if vals else None
