"""Device time of the train step by model layer, for the ``*_ms.train``
readers.

The whole runs of the step program in the traced window
(``trace.busiest_program``), their leaf ops, and each op's device time
charged to the layer scope the program's own compiled HLO gives its
instruction (``repro.obs.programs``: ``{instruction name: scope}``, built
on first request by one compile of the step with fresh metadata).  Returns
milliseconds per step for each scope of ``repro.models.LAYER_SCOPES`` that
owns an instruction of this step, None for each scope that owns none (the
layer of another family: an xLSTM step has no ``mla``), and ``unscoped``.

It reports nothing (None, and says why on stderr) where the join cannot be
trusted: a program without the map, a module name other than the traced
program's, no scope of ``LAYER_SCOPES`` owning any instruction (metadata
from a build without the scopes), or op names the map lacks on more than 1%
of the step's device time."""
from __future__ import annotations

import sys
import time
from typing import Dict, Optional

import numpy as np

from bench import trace as T

#: the share of the step's device time that may go to op names the map lacks
MAX_MISSING = 0.01


def log(msg: str) -> None:
    print(f"layer scopes: {msg}", file=sys.stderr, flush=True)


def layer_ms(run) -> Optional[Dict[str, Optional[float]]]:
    blk = run.block_of("train")
    if blk is None or run.trace is None or run.to_trace is None:
        return None
    try:
        from repro.models import LAYER_SCOPES
        from repro.obs.programs import PROGRAMS, UNSCOPED
    except ImportError as e:
        log(f"the program has no scope map ({e})")
        return None
    t0 = time.perf_counter()
    smap = PROGRAMS.scopes(blk.app)
    if smap is None:
        log(f"no step registered for {blk.app}")
        return None
    log(f"map of {len(smap.scope)} instructions of {smap.module} in "
        f"{time.perf_counter() - t0:.1f} s")
    owned = set(smap.scope.values()) & set(LAYER_SCOPES)
    if not owned:
        log(f"no scope of {LAYER_SCOPES} owns an instruction: stale metadata")
        return None
    if len(owned) < len(LAYER_SCOPES):
        log(f"scopes {sorted(set(LAYER_SCOPES) - owned)} own no instruction "
            f"of this step: they read nothing")
    ids = {d.id for d in blk.devices}
    planes = [p for p in run.trace["devices"]
              if p.rsplit(":", 1)[-1].isdigit()
              and int(p.rsplit(":", 1)[-1]) in ids]
    names = list(LAYER_SCOPES) + [UNSCOPED]
    per_plane = []
    for p in planes:
        runs = T.busiest_program(run.trace, p, *run.trace_window())
        if not runs:
            continue
        module = runs[0][0].split("(", 1)[0]
        if module != smap.module:
            log(f"{p}: busiest program {runs[0][0]} is not {smap.module}")
            return None
        ops = run.trace["devices"][p]["ops"]
        starts = np.array([r[1] for r in runs])
        ends = starts + np.array([r[2] for r in runs])
        i = np.searchsorted(starts, ops.start, side="right") - 1
        inside = (i >= 0) & (ops.start < ends[np.maximum(i, 0)]) & ~ops.box
        by_name = np.bincount(ops.code[inside], weights=ops.dur[inside],
                              minlength=len(ops.names))
        code = np.array([names.index(smap.scope[n]) if n in smap.scope
                         else len(names) for n in ops.names], np.int64)
        by_scope = np.bincount(code, weights=by_name,
                               minlength=len(names) + 1)
        total = by_scope.sum()
        missing = by_scope[-1] / total if total else 1.0
        step_ns = float(ends.sum() - starts.sum()) / len(runs)
        ranked = sorted(((by_name[k], ops.names[k]) for k in
                         np.flatnonzero(by_name)), reverse=True)
        top = ranked[:5] + [r for r in ranked[5:]
                            if smap.scope.get(r[1], UNSCOPED) == UNSCOPED][:5]
        per = 1e-6 / len(runs)
        log(f"{p}: {len(runs)} step runs of {step_ns * 1e-6:.3f} ms; leaf ops"
            f" {total * per:.3f} ms a step, {100 * missing:.4f}% not in the "
            f"map; " + ", ".join(f"{n} {v * per:.3f}"
                                 for n, v in zip(names, by_scope)))
        log(f"{p}: top ops, then top unscoped: " + ", ".join(
            f"{n} ({smap.scope.get(n)}) {v * per:.3f} ms" for v, n in top))
        if missing > MAX_MISSING:
            log(f"{p}: op names the map lacks cover {100 * missing:.2f}% of "
                f"the step, more than {100 * MAX_MISSING:g}%")
            return None
        per_plane.append(by_scope[:-1] * per)
    if not per_plane:
        return None
    mean = np.mean(per_plane, axis=0)
    return {n: float(v) if n in owned or n == UNSCOPED else None
            for n, v in zip(names, mean)}
