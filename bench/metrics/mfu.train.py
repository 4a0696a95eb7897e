"""Model step: model FLOPs of one training step (forward and backward, no
recomputation; the count of the block's family, ``bench/families/``) over the
device time of one step program times the chip's bf16 peak.  The step
program is the one that took the most device time on a train chip; its
whole runs inside the window are averaged (``bench/trace.py`` leaves out
the runs the trace's start and stop cut)."""
import sys

from bench import families
from bench import trace as T

UNIT, LAYER, MOVES, SOURCE = "%", "model step", "train_tokens_per_s", \
    "device_trace"


def read(run):
    blk = run.block_of("train")
    planes = run.devices_of("train")
    if blk is None or not planes or run.to_trace is None:
        return None
    step_flops = families.of(blk.cfg).train_step_flops(
        blk.cfg, blk.mix["global_batch"], blk.mix["seq_len"])
    shares = []
    for p in planes:
        runs = T.busiest_program(run.trace, p, *run.trace_window())
        if runs:
            per_step = sum(d for _, _, d in runs) / len(runs) * 1e-9
            print(f"mfu.train {p}: {len(runs)} whole step runs, "
                  f"{[d * 1e-9 for _, _, d in runs]} s", file=sys.stderr)
            shares.append(step_flops / (per_step * run.peaks.bf16_flops))
    return 100 * sum(shares) / len(shares) if shares else None
