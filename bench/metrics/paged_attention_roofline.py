"""Kernels: the paged-attention decode kernel's least time (the larger of
its FLOPs over the bf16 peak and its bytes over HBM bandwidth, from the
cache lengths of the tokens decoded in the traced window, counted by the
block's family, ``bench/families/``) over the summed device time of the
``paged_attention`` Pallas calls in the window."""
from bench import families
from bench import trace as T

UNIT, LAYER, MOVES, SOURCE = "%", "kernels", "itl_p95_s", "device_trace"


def read(run):
    blk = run.block_of("serve")
    planes = run.devices_of("serve")
    if blk is None or not planes or run.to_trace is None or run.load is None:
        return None
    lo, hi = run.trace_window()
    spent, n = T.kernel_ns(run.trace, planes[0], lo, hi, "paged_attention")
    ctxs = [len(s.req.prompt) + k - 1 for s in run.load.sent
            for k, t in enumerate(s.times) if k >= 1 and run.tw0 <= t <= run.tw1]
    if not n or not ctxs:
        return None
    work = families.of(blk.cfg).paged_attention_calls(blk.cfg, ctxs)
    least = work.least_time(run.peaks.bf16_flops, run.peaks.hbm_bw)[0]
    return 100 * least / (spent * 1e-9)
