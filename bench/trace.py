"""Reduce a profiler trace to busy and idle time, kernel times and the
breakdown of a traced run.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into a plain
dict: per device, the ops (own name, start, duration, whether the op holds
other ops) and the program runs, plus the host annotation that marks the
traced window.  ``save``/``read`` keep that dict on disk, which is what the
tests hold recorded.  Everything else is arithmetic on it, on the trace's
own nanosecond clock."""
from __future__ import annotations

import collections
import glob
import gzip
import json
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: host annotation that spans the traced window exactly; the harness opens
#: it with ``jax.profiler.TraceAnnotation`` and notes its own
#: ``perf_counter`` at both ends, which ties the host spans to this clock
WINDOW = "bench.window"
#: HLO ops that hold other ops (their bodies appear as ops of their own)
CONTAINERS = (" while(", " conditional(", " call(")


def op_name(text: str) -> str:
    """A TPU trace names each op by its HLO instruction text,
    ``%fusion.12 = bf16[..] fusion(..)``: the instruction's own name is the
    part before `` = ``.  The text carries no framework op path, so a
    ``jax.named_scope`` cannot be found from the trace."""
    return text.split(" = ", 1)[0].lstrip("%")


class Ops:
    """One device's ops as arrays: ``code`` indexes ``names``."""

    def __init__(self, names, code, start, dur, box):
        self.names = list(names)
        self.code = np.asarray(code, np.int64)
        self.start = np.asarray(start, np.float64)
        self.dur = np.asarray(dur, np.float64)
        self.box = np.asarray(box, bool)

    def to_json(self):
        return {"names": self.names, "code": self.code.tolist(),
                "start": self.start.tolist(), "dur": self.dur.tolist(),
                "box": self.box.tolist()}


def _ops_from_events(events) -> Ops:
    index: Dict[str, int] = {}
    code, start, dur, box = [], [], [], []
    for ev in events:
        text = ev.name
        name = op_name(text)
        code.append(index.setdefault(name, len(index)))
        start.append(ev.start_ns)
        dur.append(ev.duration_ns)
        box.append(any(c in text for c in CONTAINERS))
    names = [None] * len(index)
    for k, i in index.items():
        names[i] = k
    return Ops(names, code, start, dur, box)


def load(log_dir: str) -> Dict:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"devices": {}, "window": None}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and \
                plane.name.rsplit(":", 1)[-1].isdigit():
            lines = {ln.name: ln for ln in plane.lines}
            ops, mods = lines.get("XLA Ops"), lines.get("XLA Modules")
            out["devices"][plane.name] = {
                "ops": _ops_from_events(ops.events if ops else ()),
                "modules": [[ev.name, float(ev.start_ns),
                             float(ev.duration_ns)]
                            for ev in (mods.events if mods else ())]}
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name == WINDOW:
                        out["window"] = [float(ev.start_ns),
                                         float(ev.start_ns + ev.duration_ns)]
    return out


def save(trace: Dict, path: str) -> None:
    doc = {"window": trace["window"],
           "devices": {p: {"ops": d["ops"].to_json(),
                           "modules": d["modules"]}
                       for p, d in trace["devices"].items()}}
    with gzip.open(path, "wt") as f:
        json.dump(doc, f)


def read(path: str) -> Dict:
    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    for d in doc["devices"].values():
        d["ops"] = Ops(**d["ops"])
    return doc


# ------------------------------------------------------------ arithmetic

def _clipped(ops: Ops, lo: float, hi: float, leaves_only: bool = False):
    s, e = ops.start, ops.start + ops.dur
    keep = (e > lo) & (s < hi)
    if leaves_only:
        keep &= ~ops.box
    s2 = np.maximum(s[keep], lo)
    e2 = np.minimum(e[keep], hi)
    return keep, s2, e2


def _union(s: np.ndarray, e: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Disjoint sorted intervals covering the union of [s, e)."""
    if s.size == 0:
        return s, e
    order = np.argsort(s, kind="stable")
    s, e = s[order], e[order]
    reach = np.maximum.accumulate(e)
    new = np.empty(s.size, bool)
    new[0] = True
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    # a run ends at the running max just before the next run starts
    return s[idx], np.append(reach[idx[1:] - 1], reach[-1])


def busy_ns(trace: Dict, device: str, lo: float, hi: float) -> float:
    """Time in [lo, hi) in which some op ran on the device."""
    _, s, e = _clipped(trace["devices"][device]["ops"], lo, hi)
    us, ue = _union(s, e)
    return float(np.sum(ue - us))


def idle_gaps(trace: Dict, device: str, lo: float, hi: float
              ) -> List[Tuple[float, float]]:
    """[start, end) of each stretch of [lo, hi) with no op on the device."""
    _, s, e = _clipped(trace["devices"][device]["ops"], lo, hi)
    us, ue = _union(s, e)
    edges_s = np.concatenate([[lo], ue])
    edges_e = np.concatenate([us, [hi]])
    return [(float(a), float(b)) for a, b in zip(edges_s, edges_e) if b > a]


def kernel_ns(trace: Dict, device: str, lo: float, hi: float,
              needle: str) -> Tuple[float, int]:
    """Summed device time and count of the ops whose own name contains
    ``needle`` (a Pallas kernel's ops carry its ``name``)."""
    ops = trace["devices"][device]["ops"]
    hit = np.array([needle in n for n in ops.names], bool)
    keep, s, e = _clipped(ops, lo, hi, leaves_only=True)
    sel = hit[ops.code[keep]] if hit.size else np.zeros(0, bool)
    return float(np.sum(e[sel] - s[sel])), int(np.sum(sel))


def module_runs(trace: Dict, device: str, lo: float, hi: float,
                needle: str = "") -> List[Tuple[str, float, float]]:
    """(name, start, duration) of the device's program runs whose name
    contains ``needle`` and that lie wholly inside [lo, hi)."""
    return [(n, s, d) for n, s, d in trace["devices"][device]["modules"]
            if needle in n and s >= lo and s + d <= hi]


def busiest_program(trace: Dict, device: str, lo: float, hi: float
                    ) -> List[Tuple[str, float, float]]:
    """The whole runs inside [lo, hi) of the program that took the most
    device time there.  Each program's first and last run in the trace are
    left out: the profiler cuts a run that was under way when it started or
    stopped to the part it saw, which can lie inside the window."""
    by: Dict[str, list] = collections.defaultdict(list)
    for run in trace["devices"][device]["modules"]:
        by[run[0]].append(run)
    whole = {name: [r for r in sorted(runs, key=lambda r: r[1])[1:-1]
                    if r[1] >= lo and r[1] + r[2] <= hi]
             for name, runs in by.items()}
    whole = [r for r in whole.values() if r]
    return max(whole, key=lambda r: sum(x[2] for x in r)) if whole else []


def top_ops(trace: Dict, devices: Sequence[str], lo: float, hi: float,
            n: int = 10) -> List[List]:
    """The ops that took the most device time, by HLO instruction name,
    summed over the devices; ops that hold other ops are left out."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for dev in devices:
        ops = trace["devices"][dev]["ops"]
        keep, s, e = _clipped(ops, lo, hi, leaves_only=True)
        sums = np.bincount(ops.code[keep], weights=e - s,
                           minlength=len(ops.names))
        for i in np.flatnonzero(sums):
            tot[ops.names[i]] += float(sums[i])
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in top]


def attribute_gaps(gaps, spans, to_trace, n: int = 10) -> List[List]:
    """Total idle seconds per host activity: each gap goes to the innermost
    host span (latest start) open at its midpoint, ``"no span"`` if none.
    ``spans``: (name, t0, t1) on the host's perf_counter clock;
    ``to_trace`` maps that clock to the trace's nanoseconds."""
    import bisect
    mapped = sorted((to_trace(t0), to_trace(t1), name)
                    for name, t0, t1 in spans)
    starts = [m[0] for m in mapped]
    tot: Dict[str, float] = collections.defaultdict(float)
    for s, e in gaps:
        mid = 0.5 * (s + e)
        best = None
        i = bisect.bisect_right(starts, mid) - 1
        # the latest-starting span still open at mid; look back a bounded
        # distance (spans nest shallowly)
        for j in range(i, max(i - 512, -1), -1):
            if mapped[j][1] >= mid:
                best = mapped[j][2]
                break
        tot[best or "no span"] += (e - s) * 1e-9
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v] for k, v in top]


def clock_map(trace_window, host_window):
    """Linear map from the host's perf_counter seconds to trace ns, from
    the window annotation's two ends on both clocks."""
    (a0, a1), (h0, h1) = trace_window, host_window
    k = (a1 - a0) / max(h1 - h0, 1e-12)
    return lambda t: a0 + (t - h0) * k
