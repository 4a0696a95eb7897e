"""Compile a serve configuration's decode program and its largest prefill
(admit) program for a described TPU v5e, without the chip, and print their
memory analyses: the rehearsal that sizes a serve block's slot count.

    JAX_PLATFORMS=cpu python3 bench/described_compile.py deepseek_7b 15 16

The chip's compiler refuses a program that does not fit its 16 GB, so the
largest slot count that compiles here is the most that fit."""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import load, run, system
    system.import_program(ROOT)
    # the program picks its Pallas kernels when it believes it is on a TPU
    jax.default_backend = lambda: "tpu"
    from repro.models import model as model_lib
    from repro.serve.decode_scheduler import DecodeScheduler, paged_geometry

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = run.read_json(os.path.join(ROOT, "bench", "configs",
                                     argv[0] + ".json"))
    mix = run.read_json(os.path.join(ROOT, "bench", "traffic",
                                     "chat_steady.json"))
    mcfg = system.model_config(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    spec = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one), t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    params = spec(model_lib.abstract_params(mcfg))
    page = cfg["serve"]["page_size"]
    smax = cfg["max_position_embeddings"]
    longest = load.prefill_buckets(mix, page)[-1] + 1
    for slots in [int(a) for a in argv[1:]]:
        geo = paged_geometry(mcfg, page_size=page, n_pages=0,
                             max_slots=slots, max_seq_len=smax)
        pool = spec(jax.eval_shape(lambda: model_lib.init_paged_cache(
            mcfg, geo["n_pages"], page)))
        sch = DecodeScheduler.__new__(DecodeScheduler)
        sch.cfg, sch.sample, sch.page_size = mcfg, False, page
        try:
            dec = jax.jit(sch._make_decode(), donate_argnums=(2,)).lower(
                params, i32(slots, 1), pool,
                i32(slots, geo["pages_per_seq"]), i32(slots)).compile()
            print(f"slots {slots}: decode {dec.memory_analysis()}; "
                  f"Pallas kernel in it: "
                  f"{'tpu_custom_call' in dec.as_text()}", flush=True)
            adm = jax.jit(sch._make_admit(), donate_argnums=(2,)).lower(
                params, i32(1, longest), pool, i32(longest // page),
                i32()).compile()
            print(f"slots {slots}: admit of {longest} tokens "
                  f"{adm.memory_analysis()}", flush=True)
        except Exception as e:        # the chip's compiler refused it
            print(f"slots {slots}: refused: {str(e).splitlines()[0]}",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
