"""Device: the share of the window in which no operation ran on the serve
block's chip, from the profiler trace."""
from bench import trace as T

UNIT, LAYER, MOVES, SOURCE = "%", "device", "itl_p95_s", "device_trace"


def read(run):
    planes = run.devices_of("serve")
    if not planes or run.to_trace is None:
        return None
    lo, hi = run.trace_window()
    idle = [1 - T.busy_ns(run.trace, p, lo, hi) / (hi - lo) for p in planes]
    return 100 * sum(idle) / len(idle)
