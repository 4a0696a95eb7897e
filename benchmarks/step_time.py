"""Step-time floor: fused-optimizer + overlapped-allreduce benchmark.

Two deterministic models plus measured wall-clock rows:

* **Optimizer HBM-bytes model** — the fused AdamW kernel touches each
  param/grad/moment element exactly once per direction (one read pass, one
  write pass); the composed reference re-materializes the fp32 moments and
  the delta chain through HBM.  Rows ``opt_hbm_model_{f32,i8}_speedup_model``
  carry the modeled speedup as ``derived`` — the CI gate
  (``benchmarks/check_step_time.py``) fails if the int8-state row (the
  production 400B-class configuration, see ``dryrun.TRAIN_OVERRIDES``)
  drops below 1.5x or the f32 row below 1.0x.
* **Overlap model** — per-microbatch int8-compressed gradient allreduce
  folded into the accumulation scan vs. one uncompressed f32 allreduce after
  it: exposed communication drops from P*4B/link_bw to the un-hideable
  remainder of P*1B/link_bw behind per-microbatch compute.
* **Measured** — ``optimizer.apply`` fused ("jnp" fallback: same op fusion
  the TPU kernel locks in) vs composed reference, and ``make_train_step``
  serial vs ``overlap_comm=True`` on a 1-pod mesh.  Wall-clock on the CI
  host, so the trend gate compares medians with 10% slack.

Run via benchmarks/run.py (section ``step_time``); prints the harness CSV.
"""
import os

if __name__ == "__main__" and "XLA_FLAGS" not in os.environ:
    os.environ.setdefault("XLA_FLAGS", "")

import time

import jax
import jax.numpy as jnp
import numpy as np

import repro.configs as C
from repro.core.peaks import PEAKS, V5E
from repro.data import pipeline
from repro.models.config import ShapeConfig
from repro.train import optimizer as opt_lib
from repro.train import train_step as train_lib

LINK_BW = PEAKS[V5E].ici_link_bw        # the modelled chip is a v5e
PEAK_FLOPS = PEAKS[V5E].bf16_flops


# ------------------------------------------------------- HBM-bytes model
def opt_bytes_per_elem(bits):
    """(reference_bytes, fused_bytes) touched in HBM per parameter element.

    Fused: every operand crosses HBM once per direction — read p(2) g(4)
    m v (4+4 fp32, 1+1 int8 + amortized block scales), write p m v.
    Reference: each op in the composed chain round-trips its operands;
    with int8 state the dequantize/requantize each add a full fp32
    materialization plus the abs-max pass of the requantizer.
    """
    p, g = 2, 4                               # bf16 params, f32 grads
    if bits == 8:
        m = v = 1.03                          # int8 q + 1/256-block f32 scale
        fused = (p + g + m + v) + (p + m + v)
        ref = (
            2 * (m + 4)                       # dequant m, v: read q, write f32
            + (g + 4 + 4) + (4 + 4)           # moment update: read g,m,v write
            + (4 + 4 + p) + p                 # delta + param: read m,v,p wr p
            + 2 * (4 + 4 + 4 + m))            # requant m,v: abs-max + scale
    else:
        m = v = 4.0
        fused = (p + g + m + v) + (p + m + v)
        ref = ((g + m + v) + (m + v)          # moment update
               + (m + v + p) + p)             # delta + param write
    return ref, fused


# -------------------------------------------------------- overlap model
def overlap_exposed_comm_s(n_params, n_micro, t_grad_micro_s):
    """(serial_exposed_s, overlap_exposed_s) communication per step."""
    serial = n_params * 4 / LINK_BW                   # one f32 allreduce
    per_micro = n_params * 1 / (LINK_BW * n_micro)    # int8, per microbatch
    exposed = max(0.0, per_micro - t_grad_micro_s) * n_micro
    return serial, exposed


def timed(fn, *args, warmup=2, iters=8):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def measured_optimizer(bits):
    """Median us/call of optimizer.apply: composed reference vs fused."""
    key = jax.random.PRNGKey(0)
    params = {"stack": jax.random.normal(key, (8, 512, 512), jnp.bfloat16),
              "w": jax.random.normal(key, (512, 512), jnp.bfloat16)}
    grads = jax.tree.map(lambda p: jnp.ones(p.shape, jnp.float32), params)
    out = {}
    for label, fused in (("ref", "off"), ("fused", "jnp")):
        cfg = opt_lib.OptConfig(state_bits=bits, fused=fused)
        state = opt_lib.init(params, cfg)
        fn = jax.jit(lambda p, s, g, cfg=cfg: opt_lib.apply(cfg, p, s, g))
        out[label] = timed(fn, params, state, grads)
    return out


def measured_train_step():
    """Median us/call of the full train step: serial vs overlap_comm on a
    single-device "pod" mesh (measures the overlap machinery's overhead —
    real savings need real links; the model rows carry those)."""
    cfg = C.get_smoke("deepseek_7b")
    shape = ShapeConfig("b", "train", seq_len=64, global_batch=4,
                        microbatch=2)
    opt_cfg = opt_lib.OptConfig(warmup_steps=2, total_steps=100)
    batch = pipeline.DataIterator(cfg, shape).batch(0)
    mesh = jax.make_mesh((1,), ("pod",), axis_types=(jax.sharding.AxisType.Auto,))
    out = {}
    for label, kw in (("serial", {}),
                      ("overlap", {"overlap_comm": True, "mesh": mesh})):
        step = jax.jit(train_lib.make_train_step(cfg, shape, opt_cfg, **kw))
        state = train_lib.make_train_state(cfg, jax.random.PRNGKey(0),
                                           opt_cfg)
        out[label] = timed(lambda s, b: step(s, b)[0], state, batch)
    return out


def main():
    print("name,us_per_call,derived")
    # deterministic HBM model rows — the CI floor gates on these
    for bits, tag in ((None, "f32"), (8, "i8")):
        ref_b, fused_b = opt_bytes_per_elem(bits)
        print(f"opt_hbm_model_{tag}_speedup_model,0,{ref_b / fused_b:.3f}")
    # overlap model on a 7B-class block: 1 GB of grads, 4 microbatches,
    # per-microbatch grad compute from the compute roofline
    n_params = 1e9
    t_grad = 6 * n_params * 1024 / 4 / PEAK_FLOPS     # tokens per microbatch
    serial_s, overlap_s = overlap_exposed_comm_s(n_params, 4, t_grad)
    print(f"overlap_exposed_comm_serial,{serial_s*1e6:.0f},1.0")
    print(f"overlap_exposed_comm_overlap,{overlap_s*1e6:.0f},"
          f"{overlap_s / serial_s:.4f}")
    hidden = (serial_s - overlap_s) / serial_s if serial_s else 0.0
    print(f"overlap_hidden_frac_model,0,{hidden:.3f}")

    # measured rows (host wall-clock; the trend gate allows 10%)
    for bits, tag in ((None, "f32"), (8, "i8")):
        t = measured_optimizer(bits)
        print(f"opt_apply_{tag}_ref,{t['ref']*1e6:.0f},1.0")
        print(f"opt_apply_{tag}_fused,{t['fused']*1e6:.0f},"
              f"{t['ref'] / t['fused']:.3f}")
    t = measured_train_step()
    print(f"train_step_serial,{t['serial']*1e6:.0f},1.0")
    print(f"train_step_overlap,{t['overlap']*1e6:.0f},"
          f"{t['serial'] / t['overlap']:.3f}")


if __name__ == "__main__":
    main()
