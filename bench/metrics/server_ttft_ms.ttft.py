"""Decode scheduler: mean over the window's requests of their sessions'
``first_token_t - submitted_t`` as the scheduler records them: the wait
until the scheduler round that admits the request begins.  The scheduler
stamps ``first_token_t`` with that round's start, so the batch-1 prefill
(``prefill_ms.ttft``) is not in it."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "runtime decode scheduler", \
    "ttft_p80_s", "program_counter"


def read(run):
    if not run.sessions:
        return None
    vals = [f - s for s, f in run.sessions.values()
            if s is not None and f is not None]
    return 1e3 * statistics.fmean(vals) if vals else None
