"""Plain float32 references of the benchmark's model configurations.

Each module here reads a configuration file's sizes and nothing of the
program: it makes the weights from the seed itself, by the same key
derivation the configuration's models are initialised with, and computes in
``jax.numpy`` with every matrix product at ``Precision.HIGHEST``.  The same
code computes the control in the precision below the configuration's
(``prec="fp8"``): every matrix product's operands are rounded to an
8-bit float (e4m3) with a per-tensor scale first.

A configuration names its module by its ``reference`` key.  A module for a
family that trains exports ``init_params(cfg, seed)`` and
``loss_and_grad(params, tokens, labels, cfg, prec)``; one for a family that
serves exports ``logits_at(cfg, seed, tokens, idx, prec)``.
"""
from __future__ import annotations

import importlib


def of(cfg):
    """The reference module the configuration names."""
    return importlib.import_module(f"{__name__}.{cfg['reference']}")
