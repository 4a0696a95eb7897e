"""The training rows a seed gives: learnable token streams, one per row,
x_{t+1} = (x_t + b) mod V with the stride b and the start drawn per row,
for step ``step`` of a run seeded ``seed`` (the same rows the
configuration's data feed serves a train block with that seed)."""
from __future__ import annotations

import numpy as np

STRIDES = np.asarray([1, 2, 3, 5, 7, 11])


def lcg_batch(seed: int, step: int, rows: int, seq: int, vocab: int):
    rng = np.random.default_rng(np.uint64(seed * 1_000_003 + step))
    b = STRIDES[rng.integers(0, len(STRIDES), (rows,))]
    x0 = rng.integers(0, vocab, (rows,))
    x = ((x0[:, None] + b[:, None] * np.arange(seq + 1)[None, :])
         % vocab).astype(np.int32)
    return x[:, :-1], x[:, 1:]
