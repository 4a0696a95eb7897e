"""Device: the share of the window in which no operation ran on the train
blocks' chips, averaged over those chips, from the profiler trace."""
from bench import trace as T

UNIT, LAYER, MOVES, SOURCE = "%", "device", "train_tokens_per_s", \
    "device_trace"


def read(run):
    planes = run.devices_of("train")
    if not planes or run.to_trace is None:
        return None
    lo, hi = run.trace_window()
    idle = [1 - T.busy_ns(run.trace, p, lo, hi) / (hi - lo) for p in planes]
    return 100 * sum(idle) / len(idle)
