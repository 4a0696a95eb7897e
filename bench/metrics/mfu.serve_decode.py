"""Model step: FLOPs of the decode rounds (every weight once per decoded
token and attention over its cache; the count of the block's family,
``bench/families/``) over the device time of the decode program times the
chip's bf16 peak.  Decoded tokens are those the clients received in the
traced window after each request's first; a decode program run is a
serve-chip program run in the traced window that holds a
``paged_attention`` kernel."""
import numpy as np

from bench import families

UNIT, LAYER, MOVES, SOURCE = "%", "model step", "itl_p95_s", "device_trace"


def decode_runs(run, plane):
    lo, hi = run.trace_window()
    dev = run.trace["devices"][plane]
    ops = dev["ops"]
    hit = np.array(["paged_attention" in n for n in ops.names], bool)
    sel = hit[ops.code] & (ops.start >= lo) & (ops.start < hi)
    marks = np.sort(ops.start[sel])
    out, j = [], 0
    for name, s, d in sorted(dev["modules"], key=lambda m: m[1]):
        if s < lo or s + d > hi:
            continue
        while j < len(marks) and marks[j] < s:
            j += 1
        if j < len(marks) and marks[j] <= s + d:
            out.append(d)
    return out


def decoded_tokens(run):
    """(prompt length, token index) of each token index >= 1 that a
    client received inside the window."""
    out = []
    for s in run.load.sent:
        for k, t in enumerate(s.times):
            if k >= 1 and run.tw0 <= t <= run.tw1:
                out.append((len(s.req.prompt), k))
    return out


def read(run):
    blk = run.block_of("serve")
    planes = run.devices_of("serve")
    if blk is None or not planes or run.to_trace is None or run.load is None:
        return None
    runs = decode_runs(run, planes[0])
    toks = decoded_tokens(run)
    if not runs or not toks:
        return None
    count = families.of(blk.cfg).decode_token_flops
    flops = sum(count(blk.cfg, p + k - 1) for p, k in toks)
    return 100 * flops / (sum(runs) * 1e-9 * run.peaks.bf16_flops)
