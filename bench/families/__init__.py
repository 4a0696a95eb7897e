"""One module per configuration ``family``: what the benchmark knows of a
model family, found by the ``family`` key of a configuration file.

A family module exports ``model_config(cfg)``, the program's model
configuration built from the file's sizes (the program is imported inside
it), and the family's work counts: ``train_step_flops(cfg, B, S)`` where
the family trains, ``decode_token_flops(cfg, ctx)`` and
``paged_attention_calls(cfg, ctxs)`` where it serves.  A new family is a
new module here; nothing else of the benchmark names it."""
from __future__ import annotations

import importlib


def of(cfg):
    """The module of the configuration's family."""
    name = f"{__name__}.{cfg['family']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise ValueError(f"no model for family {cfg['family']!r}") from None
