"""Decode scheduler: mean over the window's requests of their sessions'
``first_token_t - submitted_t`` as the scheduler records them: the wait
for a slot plus the batch-1 prefill."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "runtime decode scheduler", \
    "ttft_p90_s", "program_counter"


def read(run):
    if not run.sessions:
        return None
    vals = [f - s for s, f in run.sessions.values()
            if s is not None and f is not None]
    return 1e3 * statistics.fmean(vals) if vals else None
