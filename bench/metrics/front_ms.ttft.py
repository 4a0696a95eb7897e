"""Gateway and daemon front: mean over the window's requests of the time
from the client's send to the end of the daemon's ``serve.submit`` span
(found as the parent of the ``serve.admit`` span of the request's
session).  Same process, same perf_counter."""
import statistics

UNIT, LAYER, MOVES, SOURCE = "ms", "gateway and daemon front", \
    "ttft_p80_s", "program_span"


def read(run):
    if run.load is None:
        return None
    end = {}
    for name, t0, t1, args, app, parent, sid in run.spans:
        if name == "serve.admit" and args.get("session"):
            p = run.span_by_id.get(parent)
            if p is not None and p[0] == "serve.submit":
                end[args["session"]] = p[2]
    vals = [end[s.session] - s.t_send for s in run.load.measured
            if s.session in end and s.t_send]
    return 1e3 * statistics.fmean(vals) if vals else None
