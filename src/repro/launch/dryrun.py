"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with production shardings, prove it fits (memory_analysis) and
extract roofline terms (cost_analysis + collective parse).

The two ``os.environ`` lines below MUST precede any jax import: jax locks the
device count on first init.  This module is the only place the 512-device
override is set (smoke tests and benches see the real single CPU device).

Usage:
  python -m repro.launch.dryrun --arch deepseek_7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out artifacts/dryrun
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.configs as configs
from repro.core.peaks import V5E
from repro.data import pipeline
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.models.config import ModelConfig, ShapeConfig
from repro.serve import serve_step as serve_lib
from repro.sharding import ctx as shard_ctx
from repro.sharding import plans
from repro.train import optimizer as opt_lib
from repro.train import train_step as train_lib

# per-(arch, shape) training overrides (memory fitting; see EXPERIMENTS.md
# §Dry-run).  The 100B+-scale MoE models need 8-bit Adam moments + mixed-
# precision grad accumulation to fit 16 GB/chip on the single-pod mesh.
TRAIN_OVERRIDES: Dict[str, Dict[str, Any]] = {
    "llama4_maverick_400b": {"state_bits": 8, "accum": "mixed"},
    "deepseek_v2_236b": {"state_bits": 8, "accum": "mixed"},
    # sub-1B model: TP buys nothing and the sLSTM time scan would pay
    # per-step model-axis collectives — run pure 256-way DP (ZeRO-3)
    "xlstm_350m": {"no_tp": True, "microbatch": 1},
}


def _sds(tree_abstract, sharding_tree):
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        tree_abstract, sharding_tree)


def _model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    return hlo_analysis.model_step_flops(cfg, shape)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool,
               microbatch: Optional[int] = None, plan_overrides=None):
    """Build + lower one cell.  Returns (lowered, meta dict)."""
    cfg = configs.get(arch)
    shape = configs.shape(shape_name)
    if microbatch:
        shape = shape.replace(microbatch=microbatch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = plans.MeshAxes.from_mesh(mesh)
    ctx = shard_ctx.ShardCtx(mesh, axes.dp, axes.model,
                             seq_axis=axes.dp[-1])

    if shape.kind == "train":
        over = TRAIN_OVERRIDES.get(configs.canonical(arch), {})
        if over.get("microbatch") and not microbatch:
            shape = shape.replace(microbatch=over["microbatch"])
        # no_tp folds the model axis into dp: requires global_batch %
        # dp_size == 0, which holds on the single-pod mesh (256) but not on
        # the 512-chip multi-pod mesh with batch 256 — there the default
        # TP plan stays in force.
        no_tp = bool(over.get("no_tp")) and not multi_pod
        if no_tp:
            axes = plans.MeshAxes(dp=tuple(mesh.axis_names), model="model")
            ctx = shard_ctx.ShardCtx(mesh, axes.dp, "model", tp=False)
        opt_cfg = opt_lib.OptConfig(state_bits=over.get("state_bits"))
        step_fn = train_lib.make_train_step(cfg, shape, opt_cfg,
                                            accum=over.get("accum", "f32"))
        state_abs = train_lib.abstract_train_state(cfg, opt_cfg)
        p_spec = plans.param_specs(state_abs["params"], mesh, axes,
                                   no_tp=no_tp)
        state_spec = {"params": p_spec,
                      "opt": plans.opt_state_specs(state_abs["opt"], p_spec)}
        state_shard = plans.to_shardings(state_spec, mesh)
        batch_abs = pipeline.input_specs(cfg, shape)
        b_spec = plans.batch_specs(batch_abs, mesh, axes)
        b_shard = plans.to_shardings(b_spec, mesh)
        metrics_abs = {"loss": jax.ShapeDtypeStruct((), jnp.float32),
                       "grad_norm": jax.ShapeDtypeStruct((), jnp.float32),
                       "lr": jax.ShapeDtypeStruct((), jnp.float32)}
        out_shard = (state_shard,
                     jax.tree.map(lambda _: NamedSharding(mesh, P()),
                                  metrics_abs))

        def fn(state, batch):
            with shard_ctx.use(ctx):
                return step_fn(state, batch)

        jitted = jax.jit(fn, in_shardings=(state_shard, b_shard),
                         out_shardings=out_shard, donate_argnums=(0,))
        args = (_sds(state_abs, state_shard), _sds(batch_abs, b_shard))
        lowered = jitted.lower(*args)
        entry = "train_step"

    elif shape.kind == "prefill":
        pf = serve_lib.make_prefill_step(cfg)
        params_abs = model_lib.abstract_params(cfg)
        p_spec = plans.param_specs(params_abs, mesh, axes)
        p_shard = plans.to_shardings(p_spec, mesh)
        batch_abs = pipeline.input_specs(cfg, shape)
        b_shard = plans.to_shardings(
            plans.batch_specs(batch_abs, mesh, axes), mesh)
        cache_abs = serve_lib.abstract_cache(cfg, shape.global_batch,
                                             shape.seq_len)
        if cache_abs is None:   # encoder: "prefill" = full encode, no cache
            def fn(params, batch):
                with shard_ctx.use(ctx):
                    x = model_lib.embed_inputs(params, cfg, batch)
                    logits, _, _ = model_lib.forward(
                        params, cfg, x, positions=jnp.arange(x.shape[1]))
                    return logits[:, -1]
            jitted = jax.jit(fn, in_shardings=(p_shard, b_shard))
            lowered = jitted.lower(_sds(params_abs, p_shard),
                                   _sds(batch_abs, b_shard))
        else:
            c_shard = plans.to_shardings(
                plans.cache_specs(cache_abs, cfg, mesh, axes,
                                  batch_size=shape.global_batch), mesh)

            def fn(params, batch, cache):
                with shard_ctx.use(ctx):
                    return pf(params, batch, cache)
            jitted = jax.jit(fn, in_shardings=(p_shard, b_shard, c_shard),
                             donate_argnums=(2,))
            lowered = jitted.lower(_sds(params_abs, p_shard),
                                   _sds(batch_abs, b_shard),
                                   _sds(cache_abs, c_shard))
        entry = "prefill_step"

    else:  # decode
        dec = serve_lib.make_decode_step(cfg)
        params_abs = model_lib.abstract_params(cfg)
        p_spec = plans.param_specs(params_abs, mesh, axes)
        p_shard = plans.to_shardings(p_spec, mesh)
        B = shape.global_batch
        cache_abs = serve_lib.abstract_cache(cfg, B, shape.seq_len)
        c_shard = plans.to_shardings(
            plans.cache_specs(cache_abs, cfg, mesh, axes, batch_size=B), mesh)
        dp_size = int(np.prod([mesh.shape[a] for a in axes.dp]))
        tok_spec = P(axes.dp if len(axes.dp) > 1 else axes.dp[0], None) \
            if B % dp_size == 0 else P(None, None)
        tok_shard = NamedSharding(mesh, tok_spec)
        len_shard = NamedSharding(mesh, P())

        def fn(params, token, cache, cache_len):
            with shard_ctx.use(ctx):
                return dec(params, token, cache, cache_len)

        jitted = jax.jit(fn, in_shardings=(p_shard, tok_shard, c_shard,
                                           len_shard),
                         donate_argnums=(2,))
        lowered = jitted.lower(
            _sds(params_abs, p_shard),
            jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_shard),
            _sds(cache_abs, c_shard),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=len_shard))
        entry = "decode_step"

    meta = {
        "arch": arch, "shape": shape_name, "entry": entry,
        "mesh": "2x16x16(pod,data,model)" if multi_pod else "16x16(data,model)",
        "n_chips": int(np.prod(list(mesh.shape.values()))),
        "model_flops": _model_flops(cfg, shape),
        "microbatch": shape.microbatch if shape.kind == "train" else None,
    }
    return lowered, meta


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             microbatch: Optional[int] = None) -> Dict[str, Any]:
    status = configs.cell_status(arch, shape_name)
    base = {"arch": arch, "shape": shape_name,
            "mesh": "multi" if multi_pod else "single", "status": status}
    if status != "run":
        return base
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, multi_pod=multi_pod,
                               microbatch=microbatch)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    roof = hlo_analysis.analyze(compiled, n_chips=meta["n_chips"],
                                device_kind=V5E,
                                model_flops=meta["model_flops"])
    mem_report = {}
    try:
        mem = compiled.memory_analysis()
        mem_report = {
            "argument_bytes": int(mem.argument_size_in_bytes),
            "output_bytes": int(mem.output_size_in_bytes),
            "temp_bytes": int(mem.temp_size_in_bytes),
            "alias_bytes": int(mem.alias_size_in_bytes),
            "peak_bytes_per_device": int(mem.argument_size_in_bytes
                                         + mem.output_size_in_bytes
                                         + mem.temp_size_in_bytes
                                         - mem.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        mem_report = {"error": str(e)}
    base.update(meta)
    base.update({
        "status": "ok",
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": mem_report,
        "roofline": roof.to_dict(),
    })
    return base


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatch", type=int, default=None)
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args(argv)

    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    cells = []
    if args.all:
        for a, s, _ in configs.all_cells():
            cells.append((a, s))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells.append((configs.canonical(args.arch), args.shape))

    rc = 0
    for arch, shape_name in cells:
        for mp in meshes:
            try:
                res = run_cell(arch, shape_name, multi_pod=mp,
                               microbatch=args.microbatch)
            except Exception as e:
                res = {"arch": arch, "shape": shape_name,
                       "mesh": "multi" if mp else "single",
                       "status": f"FAIL: {type(e).__name__}: {e}"}
                rc = 1
            line = json.dumps(res)
            print(line, flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(line + "\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
