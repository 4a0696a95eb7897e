"""Train-step factory: grad-accumulation microbatch scan + remat + AdamW.

The returned ``train_step(state, batch) -> (state, metrics)`` is pjit-ready:
call sites wrap it in ``jax.jit`` with in/out shardings from the plan.  One
optimizer update per call; gradients average over ``shape.microbatch``
sequential microbatches (single implicit dp all-reduce, amortized).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models import model as model_lib
from repro.models.config import ModelConfig, ShapeConfig
from repro.train import grad_compression as gc
from repro.train import optimizer as opt_lib


def make_train_state(cfg: ModelConfig, key, opt_cfg: opt_lib.OptConfig):
    params = model_lib.init_params(cfg, key)
    return {"params": params, "opt": opt_lib.init(params, opt_cfg)}


def abstract_train_state(cfg: ModelConfig, opt_cfg: opt_lib.OptConfig):
    params = model_lib.abstract_params(cfg)
    opt = jax.eval_shape(lambda p: opt_lib.init(p, opt_cfg), params)
    return {"params": params, "opt": opt}


def _split_micro(batch, n_micro: int):
    """(G, ...) -> (n_micro, G/n_micro, ...) for every leaf.  On a mesh
    with explicit axes the reshape must name its output sharding: each
    microbatch keeps the batch dim's sharding, the microbatch dim is
    unsharded."""
    def split(x):
        g = x.shape[0]
        assert g % n_micro == 0, (g, n_micro)
        sh = jax.typeof(x).sharding
        out = (sh.update(spec=P(None, *sh.spec))
               if any(a is not None for a in sh.spec) else None)
        return jax.lax.reshape(x, (n_micro, g // n_micro, *x.shape[1:]),
                               out_sharding=out)
    return jax.tree.map(split, batch)


#: leaves at least this large accumulate in bf16 under ``accum="mixed"``
#: (4M elements — the MoE expert stacks; everything smaller stays fp32)
MIXED_ACCUM_MIN_SIZE = 1 << 22


def accum_dtype(accum: str, p, threshold: int = MIXED_ACCUM_MIN_SIZE):
    """Accumulator dtype policy for one grad leaf (see ``make_train_step``)."""
    if accum == "mixed" and p.size >= threshold:
        return jnp.bfloat16
    return jnp.float32


def make_train_step(cfg: ModelConfig, shape: ShapeConfig,
                    opt_cfg: opt_lib.OptConfig, *, accum: str = "f32",
                    accum_threshold: int = MIXED_ACCUM_MIN_SIZE,
                    overlap_comm: bool = False, mesh: Optional[Mesh] = None,
                    pod_axis: str = "pod"):
    """``accum``: gradient-accumulator dtype policy across microbatches.
    "f32" — always fp32 (default); "mixed" — bf16 for large leaves
    (>= 4M elements; the MoE expert stacks), fp32 for the rest.  Mixed halves
    accumulator HBM on 100B+-param models at a ~3-bit accumulation-precision
    cost over 8 microbatches.

    ``overlap_comm``: fold the cross-pod gradient all-reduce into the
    accumulation scan.  Each microbatch's pod-local gradients are int8
    compressed-psum'd over ``pod_axis`` (``grad_compression``) while the
    *next* microbatch's backprop runs, instead of one monolithic fp32
    all-reduce of the whole accumulated tree after the scan — on the slow
    cross-pod links the reduce hides behind compute and shrinks 4x.
    Requires ``mesh`` containing ``pod_axis``, treated as a pure *replica*
    axis (params/opt replicated across pods — the federation layout; FSDP
    keeps sharding over the remaining dp axes via partial-auto shard_map).
    Quantization error is carried microbatch-to-microbatch as error
    feedback in the scan state; the final microbatch's residual is dropped
    (identically on every pod, so replicas stay bitwise in sync), bounding
    the per-step gradient error at one microbatch's quantization noise
    divided by ``n_micro``.
    """
    n_micro = max(1, shape.microbatch)
    if overlap_comm:
        assert mesh is not None and pod_axis in mesh.axis_names, \
            (pod_axis, None if mesh is None else mesh.axis_names)

    def _accum_dtype(p):
        return accum_dtype(accum, p, accum_threshold)

    def loss_of(params, mb):
        loss, metrics = model_lib.loss_fn(params, cfg, mb)
        return loss, metrics

    grad_fn = jax.value_and_grad(loss_of, has_aux=True)

    def _accum_serial(params, micro):
        """Plain accumulation: grads come out of ``grad_fn`` already
        globally reduced (GSPMD inserts the dp/pod psum per microbatch)."""
        def acc_step(carry, mb):
            g_acc, l_acc = carry
            (l, _), g = grad_fn(params, mb)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(a.dtype), g_acc, g)
            return (g_acc, l_acc + l), None

        g0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, _accum_dtype(p)), params)
        (grads, loss), _ = jax.lax.scan(acc_step, (g0, jnp.zeros(())), micro)
        return grads, loss

    def _pod_reduce(g_pod, ef):
        """Manual over ``pod_axis``: int8 compressed psum of one
        microbatch's per-pod grads.  Leaves arrive with a leading pod dim
        whose local slice has size 1.  Deliberately scan-free — any
        ``lax.scan`` inside a partial-auto shard_map trips an XLA
        manual-subgroup check on this jax, so the model never runs in
        here (see ``_accum_overlapped``)."""
        red, new_ef = gc.compressed_psum_pod(
            jax.tree.map(lambda x: x[0], g_pod),
            jax.tree.map(lambda e: e[0], ef), mesh, pod_axis)
        return red, jax.tree.map(lambda e: e[None], new_ef)

    def _accum_overlapped(params, micro):
        """Per-microbatch compressed pod reduce inside the accumulation scan
        (the reduce of microbatch i overlaps microbatch i+1's compute).

        Pod-local grads are produced in the *auto* world by vmapping
        ``grad_fn`` over an explicit leading pod dim of the microbatch (the
        batch split GSPMD would do implicitly, made structural), so the
        model's own layer scans never sit inside the manual region; only
        the small elementwise quantize+psum enters shard_map."""
        n_pods = mesh.shape[pod_axis]
        rep = jax.tree.map(lambda _: P(), params)
        pod_lead = jax.tree.map(lambda _: P(pod_axis), params)
        run = jax.shard_map(
            _pod_reduce, mesh=mesh, in_specs=(pod_lead, pod_lead),
            out_specs=(rep, pod_lead), axis_names={pod_axis},
            check_vma=False)
        pod_grad = jax.vmap(grad_fn, in_axes=(None, 0))

        def split_pod(x):
            assert x.shape[0] % n_pods == 0, (x.shape, n_pods)
            x = x.reshape(n_pods, x.shape[0] // n_pods, *x.shape[1:])
            return jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(mesh, P(pod_axis)))

        def acc_step(carry, mb):
            g_acc, l_acc, ef = carry
            (l, _), g = pod_grad(params, jax.tree.map(split_pod, mb))
            red, ef = run(g, ef)
            g_acc = jax.tree.map(
                lambda a, b: a + b.astype(a.dtype), g_acc, red)
            return (g_acc, l_acc + jnp.mean(l), ef), None

        g0 = jax.tree.map(
            lambda p: jnp.zeros(p.shape, _accum_dtype(p)), params)
        ef0 = jax.tree.map(
            lambda p: jnp.zeros((n_pods, *p.shape), jnp.float32), params)
        (grads, loss, _), _ = jax.lax.scan(
            acc_step, (g0, jnp.zeros(()), ef0), micro)
        return grads, loss

    def train_step(state, batch):
        params = state["params"]
        if n_micro == 1 and not overlap_comm:
            (loss, metrics), grads = grad_fn(params, batch)
        else:
            micro = _split_micro(batch, n_micro)
            if overlap_comm:
                grads, loss = _accum_overlapped(params, micro)
            else:
                grads, loss = _accum_serial(params, micro)
            with jax.named_scope("optimizer"):
                grads = jax.tree.map(
                    lambda g: g.astype(jnp.float32) / n_micro, grads)
            loss = loss / n_micro
            metrics = {}
        with jax.named_scope("optimizer"):
            new_params, new_opt, opt_metrics = opt_lib.apply(
                opt_cfg, params, state["opt"], grads)
        out_metrics = {"loss": loss, **opt_metrics}
        return {"params": new_params, "opt": new_opt}, out_metrics

    return train_step


def make_eval_step(cfg: ModelConfig):
    def eval_step(params, batch):
        loss, metrics = model_lib.loss_fn(params, cfg, batch)
        return {"loss": loss, **metrics}
    return eval_step
