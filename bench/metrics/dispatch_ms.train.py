"""Engine: mean duration of the train blocks' ``engine.dispatch`` spans in
the window (the host's enqueue of one train step)."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "engine", "train_tokens_per_s", \
    "program_span"


def read(run):
    apps = {b.app for b in run.blocks if b.kind == "train"}
    vals = [t1 - t0 for name, t0, t1, args, app, *_ in run.spans
            if name == "engine.dispatch" and app in apps
            and t0 >= run.w0 and t1 <= run.w1]
    return 1e3 * statistics.fmean(vals) if vals else None
