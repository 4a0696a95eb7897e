"""AOT compiles of the main path's Pallas kernels at real widths for a
described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed with JAX, compiles for
a ``v5e:2x2`` topology that is described, not attached.  It refuses what
the chip would refuse (block shapes not aligned to the tiling, more VMEM
than a kernel may use) and interpret mode cannot see.  The topology is
described inside a fixture, never while a module is imported: only one
process may load the TPU library, and the test workers each import every
test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import deepseek_7b
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.fused_adamw import fused_adamw_update
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.models import model as model_lib
from repro.serve.decode_scheduler import DecodeScheduler, paged_geometry
from repro.train import quantized_state as qs


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # compiles for a described chip are written to a persistent cache but
    # cannot be read back without one: keep the cache out of this file
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def compile_for(one_chip, fn, *shapes):
    """Compile ``fn`` for one described chip; returns the executable."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_attention_compiles_at_deepseek_7b_widths(one_chip):
    # deepseek_7b: 32 heads (MHA), head_dim 128; page 16, 8 slots x 1024,
    # the pools of 16 layers stacked
    B, H, D, page, maxp, L = 8, 32, 128, 16, 64, 16
    n_pages = B * maxp + 1
    bf = jnp.bfloat16
    c = compile_for(one_chip, paged_attention_pallas,
                    ((B, H, D), bf), ((L, n_pages, page, H, D), bf),
                    ((L, n_pages, page, H, D), bf), ((B, maxp), jnp.int32),
                    ((B,), jnp.int32), ((), jnp.int32))
    # one page of K and V at a time: no whole-sequence VMEM scratch
    assert c.memory_analysis().temp_size_in_bytes < 64 << 20


def test_paged_attention_compiles_for_gqa(one_chip):
    # 4 query heads per kv head (mistral_nemo_12b's 32/8 grouping)
    B, page, maxp, Hkv, L = 8, 16, 64, 8, 4
    n_pages = B * maxp + 1
    bf = jnp.bfloat16
    compile_for(one_chip, paged_attention_pallas,
                ((B, 32, 128), bf), ((L, n_pages, page, Hkv, 128), bf),
                ((L, n_pages, page, Hkv, 128), bf), ((B, maxp), jnp.int32),
                ((B,), jnp.int32), ((), jnp.int32))


def test_paged_decode_program_writes_the_pool_in_place(one_chip,
                                                       monkeypatch):
    """The serve cell's decode program (deepseek_7b's first 16 layers, 15
    slots of 1024 tokens, page 16) keeps the donated KV pool in place: its
    scratch holds no stacked copy of the pool, and its output aliases the
    whole pool."""
    # the model picks its Pallas kernels when it believes it is on a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = deepseek_7b.CONFIG.replace(n_layers=16)
    slots, page = 15, 16
    geo = paged_geometry(cfg, page_size=page, n_pages=0, max_slots=slots,
                         max_seq_len=1024)
    spec = lambda t: jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=one_chip), t)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    pool = spec(jax.eval_shape(lambda: model_lib.init_paged_cache(
        cfg, geo["n_pages"], page)))
    sch = DecodeScheduler.__new__(DecodeScheduler)
    sch.cfg, sch.sample, sch.page_size = cfg, False, page
    dec = jax.jit(sch._make_decode(), donate_argnums=(2,)).lower(
        spec(model_lib.abstract_params(cfg)), i32(slots, 1), pool,
        i32(slots, geo["pages_per_seq"]), i32(slots)).compile()
    mem = dec.memory_analysis()
    k_pool = pool["k"].size * pool["k"].dtype.itemsize
    assert pool["k"].shape == (16, 961, 16, 32, 128)
    assert mem.temp_size_in_bytes < k_pool, mem
    assert mem.alias_size_in_bytes >= 2 * k_pool, mem


def test_flash_attention_compiles_32_heads_seq_2048(one_chip):
    bf = jnp.bfloat16
    compile_for(one_chip, flash_attention_pallas,
                ((1, 32, 2048, 128), bf), ((1, 32, 2048, 128), bf),
                ((1, 32, 2048, 128), bf))


@pytest.mark.parametrize("d", [1024, 4096])
def test_rmsnorm_compiles(one_chip, d):
    bf = jnp.bfloat16
    compile_for(one_chip, rmsnorm_pallas, ((8, 2048, d), bf), ((d,), bf))


@pytest.mark.parametrize("bits", [None, 8])
def test_fused_adamw_compiles_1024x4096(one_chip, bits):
    shape = (1024, 4096)
    nb = shape[-1] // qs.BLOCK

    def update(p, g, mq, ms, vq, vs):
        m = {"q": mq, "s": ms} if bits == 8 else mq
        v = {"q": vq, "s": vs} if bits == 8 else vq
        return fused_adamw_update(p, g, m, v, lr=3e-4, scale=1.0, bc1=0.1,
                                  bc2=0.05, b1=0.9, b2=0.95, eps=1e-8,
                                  weight_decay=0.1, apply_wd=True)

    moment = (shape, jnp.int8 if bits == 8 else jnp.float32)
    scales = ((shape[0], nb), jnp.float32)
    compile_for(one_chip, update, (shape, jnp.bfloat16),
                (shape, jnp.float32), moment, scales, moment, scales)


def test_ssd_scan_compiles(one_chip):
    # zamba2-like head layout: 8 heads x head_dim 64, state 64, chunk 256
    Bt, S, H, P, N = 2, 2048, 8, 64, 64
    bf, f32 = jnp.bfloat16, jnp.float32
    compile_for(one_chip, lambda *a: ssd_scan_pallas(*a, chunk=256)[0],
                ((Bt, S, H, P), bf), ((Bt, S, H), f32), ((H,), f32),
                ((Bt, S, N), bf), ((Bt, S, N), bf), ((H,), f32))
