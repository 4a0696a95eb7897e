"""Optimizer: device milliseconds a train step spends in the ``optimizer``
scope (the gradient scaling over microbatches, global-norm clipping and
the AdamW update).  The step program's leaf ops in the traced window,
charged to scopes by the program's own compiled HLO (``bench/scopes.py``),
per whole step run."""
from bench import scopes

UNIT, LAYER, MOVES, SOURCE = "ms", "optimizer", "train_tokens_per_s", \
    "device_trace"


def read(run):
    ms = scopes.layer_ms(run)
    return None if ms is None else ms["optimizer"]
