"""The one traffic generator: reads a mix's data file and makes the work a
run offers, from the seed.

Serve mixes are open-loop.  Every seed gets the same requests at the same
times: the prompt lengths, output lengths and inter-arrival gaps are the
quantiles of the mix's distributions on a fixed grid, put in one order that
the mix's ``order_seed`` draws.  The seed draws only the prompts' token ids.
An order of the seed's own would change how the arrivals bunch, and with
them the queue for the block's slots and the TTFT tail: a run would then
measure the seed and not the system."""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Request:
    idx: int
    due_s: float              # offset from the window's start
    prompt: List[int]
    max_new_tokens: int
    measured: bool            # due inside the window


def _quantiles(n: int, spec: Dict) -> np.ndarray:
    """n lognormal lengths at the mid-quantiles (i + 1/2)/n, clipped."""
    nd = statistics.NormalDist(math.log(spec["median"]), spec["sigma"])
    vals = [math.exp(nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(int)


def _gaps(n: int, rate: float) -> np.ndarray:
    """n exponential inter-arrival gaps at the mid-quantiles."""
    return np.array([-math.log(1.0 - (i + 0.5) / n) / rate
                     for i in range(n)])


def _segment(rng, order, mix: Dict, vocab: int, n: int, t0: float,
             span: float, idx0: int, measured: bool) -> List[Request]:
    """n requests due in [t0, t0 + span): the gaps, scaled so that they
    fill the span exactly, at the mix's mean rate n / span.  ``order``
    puts the lengths and gaps in order, ``rng`` draws the token ids."""
    if n <= 0:
        return []
    plens = order.permutation(_quantiles(n, mix["prompt_tokens"]))
    olens = order.permutation(_quantiles(n, mix["output_tokens"]))
    gaps = order.permutation(_gaps(n, mix["rate_per_s"]))
    due = t0 + np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (
        span / gaps.sum())
    out = []
    for i in range(n):
        prompt = rng.integers(1, vocab, size=int(plens[i])).tolist()
        out.append(Request(idx0 + i, float(due[i]), prompt, int(olens[i]),
                           measured))
    return out


def serve_requests(mix: Dict, vocab: int, seed: int, seconds: float,
                   tail_s: float) -> List[Request]:
    """The requests of a window of ``seconds`` (``measured``), then
    ``tail_s`` more of the same mix that keep the load on while the
    window's last requests finish."""
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix["order_seed"])
    rate = mix["rate_per_s"]
    n_w = max(1, int(round(rate * seconds)))
    win = _segment(rng, order, mix, vocab, n_w, 0.0, seconds, 0, True)
    n_t = int(round(rate * tail_s))
    tail = _segment(rng, order, mix, vocab, n_t, seconds, tail_s, n_w,
                    False)
    return win + tail


def prefill_buckets(mix: Dict, page_size: int) -> List[int]:
    """Every prompt length, in tokens, whose prefill program the mix can
    reach: one per page count that a prompt of min..max tokens needs
    (pages for the prompt and the first decode write)."""
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    needs = sorted({p // page_size + 1 for p in range(lo, hi + 1)})
    return [n * page_size - 1 for n in needs]
