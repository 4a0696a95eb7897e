"""Post-compile HLO analysis: collective-traffic extraction + roofline terms.

``cost_analysis()`` supplies FLOPs and bytes-accessed; collective bytes are
not in it, so we parse the optimized HLO text and sum operand sizes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute /
collective-broadcast op (assignment convention: operand bytes).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

import numpy as np

from repro.core import peaks as peaks_lib

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute", "collective-broadcast")

# e.g.  bf16[16,4096,5120]{2,1,0}
_SHAPE_RE = re.compile(r"\b([a-z]+\d+(?:e\d+m\d+(?:fn)?)?|pred)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?:\([^)]*\)|[^=]+?)\s*"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute|"
    r"collective-broadcast)\(", re.M)


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims.strip():
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    bytes_by_kind: Dict[str, int]

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    @property
    def total_count(self) -> int:
        return sum(self.counts.values())


def collective_stats(hlo_text: str) -> CollectiveStats:
    counts: Dict[str, int] = {}
    total: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        kind = m.group(1).replace("-start", "")
        # operand shapes = every shape appearing AFTER the opcode's '('
        after = line[m.end():]
        op_bytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(after))
        counts[kind] = counts.get(kind, 0) + 1
        total[kind] = total.get(kind, 0) + op_bytes
    return CollectiveStats(counts=counts, bytes_by_kind=total)


@dataclasses.dataclass
class Roofline:
    hlo_flops: float             # total FLOPs across all devices
    hlo_bytes: float             # total HBM bytes accessed across devices
    collective_bytes: float      # summed collective operand bytes (per device program)
    n_chips: int
    peaks: peaks_lib.ChipPeaks   # the target chip's (``core.peaks``)
    model_flops: float = 0.0
    bytes_per_device: float = 0.0

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.n_chips * self.peaks.bf16_flops)

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / (self.n_chips * self.peaks.hbm_bw)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (self.n_chips
                                       * self.peaks.ici_link_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time = max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_frac(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved at the modeled step time:
        useful (model) FLOPs / (step_time * peak).  1.0 = compute-bound with
        zero waste."""
        denom = self.step_time_s * self.n_chips * self.peaks.bf16_flops
        return self.model_flops / denom if denom else 0.0

    xla_cost: Optional[Dict] = None
    coll_detail: Optional[Dict] = None

    def to_dict(self) -> Dict:
        return {
            "xla_cost": self.xla_cost,
            "coll_detail": self.coll_detail,
            "hlo_flops": self.hlo_flops,
            "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "n_chips": self.n_chips,
            "model_flops": self.model_flops,
            "bytes_per_device": self.bytes_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "step_time_s": self.step_time_s,
            "useful_flops_frac": self.useful_flops_frac,
            "roofline_fraction": self.roofline_fraction,
        }


def model_step_flops(cfg, shape) -> float:
    """Analytic useful-FLOPs per step: 6ND for training, 2ND for inference
    (N = active non-embedding params, D = tokens touched per step).  The
    numerator of MFU — what the Monitor divides by measured step time."""
    from repro.models import model as model_lib
    n_active = model_lib.count_active_params(cfg)
    # exclude the embedding gather (not matmul flops); keep lm_head
    n_eff = max(n_active - cfg.vocab_size * cfg.d_model, 1)
    if shape.kind == "train":
        return 6.0 * n_eff * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n_eff * shape.global_batch * shape.seq_len
    return 2.0 * n_eff * shape.global_batch      # decode: one token per seq


def block_roofline(cfg, shape, n_chips: int, device_kind: str) -> Dict:
    """Roofline model for a live block, for ``Monitor.set_roofline``.

    Prefers the dry-run artifact for this (arch, shape) cell — the full
    compute/memory/collective model from the compiled HLO — and falls back
    to the analytic compute-bound floor (model FLOPs / chips x peak) when no
    sweep has been run.  A ``device_kind`` missing from ``core.peaks`` has
    no peak: the model then carries no ``peak_flops`` and no step-time
    floor, so the block reports no MFU.
    """
    flops = model_step_flops(cfg, shape)
    out = {"model_flops": flops, "n_chips": int(n_chips),
           "device_kind": device_kind, "source": "analytic",
           "bottleneck": "compute"}
    peaks = peaks_lib.lookup(device_kind)
    if peaks is None:
        return out
    out.update(peak_flops=peaks.bf16_flops,
               step_time_s=flops / (max(1, n_chips) * peaks.bf16_flops))
    cell = dryrun_roofline(getattr(cfg, "name", None),
                           getattr(shape, "name", None))
    if cell:
        # per-chip terms from the sweep's mesh scale to this block's size:
        # step time is per-device under perfect balance, so it carries over
        out.update({"source": "dryrun",
                    "step_time_s": cell["step_time_s"],
                    "bottleneck": cell.get("bottleneck", "compute"),
                    "model_flops": cell.get("model_flops", flops) or flops})
    return out


def dryrun_roofline(arch: Optional[str],
                    shape_name: Optional[str]) -> Optional[Dict]:
    """Look up the dry-run sweep's roofline dict for one cell, or None.

    Reads ``artifacts/dryrun/*.jsonl`` (written by ``repro.launch.dryrun
    --all --out``; tabulated by ``benchmarks/roofline_report.py``).  Single-
    pod cells win over multi-pod when both exist."""
    if not arch or not shape_name:
        return None
    import glob as _glob
    import json as _json
    import os as _os
    art = _os.path.join(_os.path.dirname(__file__), "..", "..", "..",
                        "artifacts", "dryrun")
    best = None
    for path in sorted(_glob.glob(_os.path.join(art, "*.jsonl"))):
        try:
            with open(path) as f:
                for line in f:
                    try:
                        d = _json.loads(line)
                    except ValueError:
                        continue
                    if (d.get("arch") == arch
                            and d.get("shape") == shape_name
                            and d.get("status") == "ok"
                            and "roofline" in d):
                        if best is None or d.get("mesh") == "single":
                            best = d["roofline"]
        except OSError:
            continue
    return best


def analyze(compiled, *, n_chips: int, device_kind: str,
            model_flops: float = 0.0) -> Roofline:
    """Roofline terms from a compiled SPMD executable.

    The compiled program is per-device; the trip-count-aware HLO walker
    (``hlo_parse``) supplies per-device flops / HBM bytes / collective operand
    bytes, which are scaled by ``n_chips`` into global quantities.  The three
    roofline terms then divide by (chips x the ``device_kind``'s per-chip
    peak), i.e. they equal the per-device time under perfect balance.
    XLA's own ``cost_analysis()`` counts while bodies once and is kept only
    as a cross-check field.
    """
    from repro.launch import hlo_parse
    text = compiled.as_text()
    costs = hlo_parse.analyze_text(text)
    xla_cost = {}
    try:
        c = compiled.cost_analysis()
        if isinstance(c, list):
            c = c[0]
        xla_cost = {"flops": float(c.get("flops", 0.0)),
                    "bytes_accessed": float(c.get("bytes accessed", 0.0))}
    except Exception:
        pass
    bpd = 0.0
    try:
        mem = compiled.memory_analysis()
        bpd = float(mem.temp_size_in_bytes + mem.argument_size_in_bytes
                    + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    except Exception:
        pass
    r = Roofline(hlo_flops=costs.flops * n_chips,
                 hlo_bytes=costs.hbm_bytes * n_chips,
                 collective_bytes=costs.total_coll_bytes * n_chips,
                 n_chips=n_chips, peaks=peaks_lib.peaks_for(device_kind),
                 model_flops=model_flops,
                 bytes_per_device=bpd)
    r.xla_cost = xla_cost
    r.coll_detail = {"bytes_by_kind": dict(costs.coll_bytes),
                     "counts": dict(costs.coll_counts)}
    return r
