"""Continuous-batching serve data plane.

Layers under test, bottom-up:

* the Pallas paged-attention kernel vs ``ref.attention`` in interpret
  mode, to float32 rounding (the kernel sweeps pages with an online
  softmax, so its sums run in a different order than the reference's),
  and on a stacked pool, by layer index, bit for bit against the same
  arithmetic on that layer's pool alone;
* ``DecodeScheduler`` semantics: bit-identical paged-vs-dense greedy
  decode, ``cache_len=0`` (the falsy-zero trap the analysis rule pack
  hunts), page-boundary crossing, full-pool admission refusal,
  evict/requeue determinism, slot isolation;
* the ``BlockRuntime`` session API + daemon/engine event plumbing
  (``generate``/``session`` kinds);
* ``POST /v1/blocks/<id>/generate`` over real HTTP: SSE stream, long-poll
  JSON, 429 rate-limit storm, 413 body cap;
* checkpointed paged state: in-flight sessions survive preempt/resume,
  including resume on a different mesh geometry (subprocess).
"""
import functools
import json
import os
import subprocess
import sys
import textwrap
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import ops, ref
from repro.kernels.paged_attention import paged_attention_pallas
from repro.models import model as model_lib
from repro.models.config import AttentionConfig, ModelConfig, ShapeConfig
from repro.serve.decode_scheduler import DecodeScheduler, PagePool

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
KEY = jax.random.PRNGKey(7)


def tiny_cfg(**kw):
    base = dict(name="serve_t", family="dense", n_layers=2, d_model=32,
                vocab_size=64, d_ff=64,
                attention=AttentionConfig(n_heads=4, n_kv_heads=2,
                                          head_dim=8),
                param_dtype="float32")
    base.update(kw)
    return ModelConfig(**base)


@pytest.fixture(scope="module")
def cfg():
    return tiny_cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return model_lib.init_params(cfg, jax.random.PRNGKey(0))


def drain(sch, cap=500):
    ems = []
    for _ in range(cap):
        if not sch.has_work:
            return ems
        ems.extend(sch.step())
    raise AssertionError("scheduler did not drain")


def greedy_dense(cfg, params, prompt, max_new, smax):
    """Reference decode: dense prefill + per-token decode_step, greedy."""
    cache = model_lib.init_cache(cfg, 1, smax)
    logits, cache = model_lib.prefill(
        cfg=cfg, params=params, batch={"tokens": jnp.asarray([prompt],
                                                             jnp.int32)},
        cache=cache)
    toks = [int(jnp.argmax(logits[0], -1))]
    for i in range(max_new - 1):
        lg, cache = model_lib.decode_step(
            params, cfg, jnp.asarray([[toks[-1]]], jnp.int32), cache,
            jnp.int32(len(prompt) + i))
        toks.append(int(jnp.argmax(lg[0], -1)))
    return toks


# ======================================================== kernel vs ref

def make_paged(key, B, Hkv, D, Dv, page, maxp, n_pages, lens):
    """Random pool + per-slot tables; page 0 is the (garbage) trash page."""
    ks = jax.random.split(key, 3)
    k_pages = jax.random.normal(ks[0], (n_pages, page, Hkv, D), jnp.float32)
    v_pages = jax.random.normal(ks[1], (n_pages, page, Hkv, Dv), jnp.float32)
    rng = np.random.default_rng(3)
    free = list(rng.permutation(np.arange(1, n_pages)))
    table = np.zeros((B, maxp), np.int32)      # unallocated -> trash page
    for b, ln in enumerate(lens):
        for j in range((ln + page - 1) // page):
            table[b, j] = free.pop()
    return k_pages, v_pages, jnp.asarray(table), jnp.asarray(lens, jnp.int32)


def gather_dense(pages, table, B, S, H):
    """(B, S, Hkv, D) dense view of each slot's gathered pages."""
    return pages[table].reshape(B, S, H, -1).swapaxes(1, 2)


@pytest.mark.parametrize("B,Hq,Hkv,D,Dv,page,maxp",
                         [(3, 4, 2, 16, 16, 8, 2),    # GQA
                          (2, 4, 1, 16, 8, 4, 4),     # MQA, Dv != D
                          (1, 2, 2, 8, 8, 16, 1)])    # MHA, single page
def test_paged_kernel_bitwise_vs_ref_full_slots(B, Hq, Hkv, D, Dv, page,
                                                maxp):
    """Every slot filled to capacity: the length mask is all-true, so the
    kernel must reproduce ``ref.attention`` on the gathered dense layout
    to float32 rounding."""
    S = page * maxp
    lens = [S] * B
    k_pages, v_pages, table, seq_lens = make_paged(
        KEY, B, Hkv, D, Dv, page, maxp, n_pages=B * maxp + 2, lens=lens)
    q = jax.random.normal(jax.random.fold_in(KEY, 9), (B, Hq, D))
    got = paged_attention_pallas(q, k_pages[None], v_pages[None], table,
                                 seq_lens, 0, interpret=True)
    kd = gather_dense(k_pages, table, B, S, Hkv)
    vd = gather_dense(v_pages, table, B, S, Hkv)
    want = ref.attention(q[:, :, None], kd, vd, causal=False)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-5)


def test_paged_kernel_ragged_lens_and_trash_page():
    """Ragged fills (1 token, mid-page, page boundary): the kernel must
    match the jnp production path and ``ref.attention`` on the *truncated*
    cache to float32 rounding — rows past ``seq_lens`` (garbage pages,
    trash page) must not leak in."""
    B, Hq, Hkv, D, page, maxp = 4, 4, 2, 16, 4, 3
    lens = [1, 5, 8, 12]                      # mid-page / boundary / full
    k_pages, v_pages, table, seq_lens = make_paged(
        jax.random.fold_in(KEY, 1), B, Hkv, D, D, page, maxp,
        n_pages=B * maxp + 1, lens=lens)
    # poison the trash page: a masking bug would surface immediately
    k_pages = k_pages.at[0].set(1e4)
    v_pages = v_pages.at[0].set(-1e4)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Hq, 1, D))
    got = ops.paged_attention(q, k_pages[None], v_pages[None], table,
                              seq_lens, 0, impl="pallas")
    want = ops.paged_attention(q, k_pages[None], v_pages[None], table,
                               seq_lens, 0, impl="jnp")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-6, rtol=1e-5)
    for b, ln in enumerate(lens):             # vs truncated naive oracle
        kd = gather_dense(k_pages, table[b:b + 1], 1, page * maxp, Hkv)
        vd = gather_dense(v_pages, table[b:b + 1], 1, page * maxp, Hkv)
        w = ref.attention(q[b:b + 1], kd[:, :, :ln], vd[:, :, :ln],
                          causal=False)
        np.testing.assert_allclose(np.asarray(got[b:b + 1]), np.asarray(w),
                                   atol=3e-5, rtol=3e-4)


def _one_pool_kernel(pt_ref, sl_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                     l_scr, acc_scr, *, pages_per_seq, page, groups, scale):
    b, j = pl.program_id(0), pl.program_id(1)
    seq_len = sl_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1.0e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * page < seq_len)
    def _body():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (page, k.shape[1], 1), 0)
        live = pos < seq_len
        for g in range(groups):
            q = q_ref[0, g].astype(jnp.float32)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
            s = jnp.where(live, s, -1.0e30)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.exp(s - m_new[None]) * live.astype(jnp.float32)
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=0)
            acc_scr[g] = acc_scr[g] * corr + jnp.sum(p * v, axis=0)
            m_scr[g] = m_new

    @pl.when(j == pages_per_seq - 1)
    def _finalize():
        for g in range(groups):
            l = jnp.where(l_scr[g] == 0.0, 1.0, l_scr[g])
            o_ref[0, g] = (acc_scr[g] / l).astype(o_ref.dtype)


def paged_attention_one_pool(q, k_pages, v_pages, table, seq_lens):
    """The paged kernel as it was before pools were stacked: the same
    arithmetic on one layer's ``(P, page, Hkv, D)`` pool, its page ids the
    whole block index.  q: (B, Hq, D).  Returns (B, Hq, Dv)."""
    B, Hq, D = q.shape
    _, page, Hkv, _ = k_pages.shape
    Dv = v_pages.shape[-1]
    G, maxp = Hq // Hkv, table.shape[1]
    qg = q.reshape(B, Hkv, G, D).swapaxes(1, 2)

    def kv_index(b, j, pt_ref, sl_ref):
        last = jnp.maximum(sl_ref[b] - 1, 0) // page
        return (pt_ref[b, jnp.minimum(j, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B, maxp),
        in_specs=[pl.BlockSpec((1, G, Hkv, D), lambda b, j, *_: (b, 0, 0, 0)),
                  pl.BlockSpec((1, page, Hkv, D), kv_index),
                  pl.BlockSpec((1, page, Hkv, Dv), kv_index)],
        out_specs=pl.BlockSpec((1, G, Hkv, Dv),
                               lambda b, j, *_: (b, 0, 0, 0)),
        scratch_shapes=[pltpu.VMEM((G, Hkv, 1), jnp.float32),
                        pltpu.VMEM((G, Hkv, 1), jnp.float32),
                        pltpu.VMEM((G, Hkv, Dv), jnp.float32)])
    kernel = functools.partial(_one_pool_kernel, pages_per_seq=maxp,
                               page=page, groups=G, scale=1.0 / np.sqrt(D))
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, Hkv, Dv), q.dtype),
        interpret=True)(table, seq_lens, qg, k_pages, v_pages)
    return out.swapaxes(1, 2).reshape(B, Hq, Dv)


@pytest.mark.parametrize("L", [1, 2, 5])
def test_paged_kernel_layer_index_bitwise_vs_one_pool(L):
    """The kernel on a stacked ``(L, P, page, Hkv, D)`` pool, told a layer,
    equals the one-pool arithmetic on ``pool[layer]`` bit for bit, for
    every layer: ragged fills, a slot at a page boundary, and an idle slot
    whose table row is all trash page."""
    B, Hq, Hkv, D, Dv, page, maxp = 5, 4, 2, 16, 8, 4, 3
    lens = [1, 6, 8, 12, 0]          # mid-page / boundary / full / idle
    n_pages = B * maxp + 1
    ks = jax.random.split(jax.random.fold_in(KEY, 20 + L), 3)
    k_pool = jax.random.normal(ks[0], (L, n_pages, page, Hkv, D), jnp.float32)
    v_pool = jax.random.normal(ks[1], (L, n_pages, page, Hkv, Dv),
                               jnp.float32)
    _, _, table, seq_lens = make_paged(KEY, B, Hkv, D, Dv, page, maxp,
                                       n_pages=n_pages, lens=lens)
    q = jax.random.normal(ks[2], (B, Hq, D), jnp.float32)
    for layer in range(L):
        got = paged_attention_pallas(q, k_pool, v_pool, table, seq_lens,
                                     jnp.int32(layer), interpret=True)
        want = paged_attention_one_pool(q, k_pool[layer], v_pool[layer],
                                        table, seq_lens)
        assert np.array_equal(np.asarray(got), np.asarray(want)), layer


def test_paged_decode_model_matches_dense_bitwise(cfg, params):
    """``decode_step_paged`` == ``decode_step`` bit-for-bit when the paged
    layout mirrors a contiguous dense cache of the same attention width
    (equal S is required: softmax reduction width changes the bits)."""
    page, maxp = 4, 4
    smax = page * maxp
    prompt = [3, 1, 4, 1, 5]
    cache = model_lib.init_cache(cfg, 1, smax)
    logits, cache = model_lib.prefill(
        cfg=cfg, params=params,
        batch={"tokens": jnp.asarray([prompt], jnp.int32)}, cache=cache)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)

    pool = model_lib.init_paged_cache(cfg, n_pages=maxp + 1, page_size=page)
    pool = model_lib.write_prefill_to_pages(pool, cache, [1, 2, 3, 4], page)
    table = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    for i in range(3):
        want, cache = model_lib.decode_step(params, cfg, tok, cache,
                                            jnp.int32(len(prompt) + i))
        got, pool = model_lib.decode_step_paged(
            params, cfg, tok, pool, table,
            jnp.asarray([len(prompt) + i], jnp.int32))
        assert np.array_equal(np.asarray(got), np.asarray(want))
        tok = jnp.argmax(got, -1)[:, None].astype(jnp.int32)


# ================================================= scheduler semantics

def test_paged_greedy_decode_bit_identical_to_dense(cfg, params):
    """End-to-end token identity, including the admission prefill with
    ``cache_len=0`` (falsy-zero trap: a ``0`` must mean "empty cache",
    never "no cache") and prompts of every page-alignment flavour."""
    page, max_seq = 4, 32
    prompts = [[9], [3, 1, 4], [3, 1, 4, 1], [3, 1, 4, 1, 5]]
    sch = DecodeScheduler(cfg, params, page_size=page, n_pages=0,
                          max_slots=len(prompts), max_seq_len=max_seq)
    sids = [sch.submit(p, max_new_tokens=10) for p in prompts]
    drain(sch)
    for sid, p in zip(sids, prompts):
        assert sch.sessions[sid].generated == greedy_dense(
            cfg, params, p, 10, max_seq), f"prompt {p} diverged"


def test_page_boundary_crossing_grows_allocation(cfg, params):
    """Generation across page boundaries allocates pages on demand and the
    tokens stay identical to the dense path (an off-by-one at the
    boundary would corrupt the row the next K/V write lands in)."""
    page = 4
    prompt = [3, 1, 4]                       # 1 page; crosses at pos 4, 8
    sch = DecodeScheduler(cfg, params, page_size=page, n_pages=0,
                          max_slots=1, max_seq_len=16)
    sid = sch.submit(prompt, max_new_tokens=9)   # final pos 11 -> 3 pages
    sch.step()
    assert len(sch.sessions[sid].pages) == 1
    peak = 1
    while sch.has_work:
        sch.step()
        peak = max(peak, len(sch.sessions[sid].pages))
    assert peak == 3                          # grown page by page, on demand
    assert sch.sessions[sid].pages == []      # reclaimed on finish
    assert sch.sessions[sid].generated == greedy_dense(
        cfg, params, prompt, 9, 16)


def test_full_pool_admission_refusal_then_progress(cfg, params):
    """All-or-nothing admission: with every page owned by the running
    session the queued one must NOT be half-admitted — it waits, then
    completes once the pool frees."""
    page = 4
    # 3 usable pages (page 0 reserved): session A needs 2 on admission
    sch = DecodeScheduler(cfg, params, page_size=page, n_pages=4,
                          max_slots=2, max_seq_len=12)
    a = sch.submit([1, 2, 3, 4, 5], max_new_tokens=6)   # 2 pages
    b = sch.submit([6, 7, 8, 9, 10], max_new_tokens=6)  # needs 2: refused
    sch.step()
    assert sch.sessions[a].state == "running"
    assert sch.sessions[b].state == "queued"
    assert sch.pages.available == 1           # partial grab would show here
    drain(sch)
    assert sch.sessions[a].state == "done"
    assert sch.sessions[b].state == "done"
    assert sch.sessions[b].generated == greedy_dense(
        cfg, params, [6, 7, 8, 9, 10], 6, 12)
    assert sch.pages.available == 3           # everything reclaimed


def test_eviction_requeue_resumes_exactly(cfg, params):
    """Pool pressure evicts the shortest-progress victim; the evicted
    session re-queues with its generated prefix as prompt context and
    must finish with the same tokens as an uninterrupted run."""
    sch = DecodeScheduler(cfg, params, page_size=4, n_pages=6,
                          max_slots=3, max_seq_len=32)
    sids = [sch.submit([s, s + 1, s + 2], max_new_tokens=12)
            for s in (1, 4, 7)]
    drain(sch)
    assert sch.evictions > 0                  # the pressure actually hit
    assert all(sch.sessions[s].state == "done" for s in sids)
    for sid, s in zip(sids, (1, 4, 7)):
        assert sch.sessions[sid].generated == greedy_dense(
            cfg, params, [s, s + 1, s + 2], 12, 32)


def test_idle_slots_do_not_contaminate(cfg, params):
    """A lone session surrounded by idle slots (``seq_lens == 0``, trash
    page table rows) must decode exactly as a max_slots=1 scheduler —
    zero-length masking treating 0 as "no mask" would leak garbage."""
    solo = DecodeScheduler(cfg, params, page_size=4, n_pages=0,
                           max_slots=1, max_seq_len=16)
    wide = DecodeScheduler(cfg, params, page_size=4, n_pages=0,
                           max_slots=8, max_seq_len=16)
    a = solo.submit([5, 6, 7], max_new_tokens=8)
    b = wide.submit([5, 6, 7], max_new_tokens=8)
    drain(solo), drain(wide)
    assert solo.sessions[a].generated == wide.sessions[b].generated


def test_submit_validation_and_eos(cfg, params):
    sch = DecodeScheduler(cfg, params, page_size=4, n_pages=0,
                          max_slots=2, max_seq_len=8)
    with pytest.raises(ValueError):
        sch.submit([], max_new_tokens=2)                # empty prompt
    with pytest.raises(ValueError):
        sch.submit([1] * 8, max_new_tokens=2)           # >= max_seq_len
    with pytest.raises(ValueError):
        sch.submit([1], max_new_tokens=0)
    sid = sch.submit([1, 2], max_new_tokens=6)
    with pytest.raises(ValueError):
        sch.submit([3], sid=sid)                        # duplicate id
    # eos_id == first generated token -> finishes after exactly 1 token
    first = greedy_dense(cfg, params, [1, 2], 1, 8)[0]
    eos = sch.submit([1, 2], max_new_tokens=6, eos_id=first)
    drain(sch)
    assert sch.sessions[eos].generated == [first]
    assert sch.sessions[eos].finish_reason == "eos"
    assert sch.sessions[sid].finish_reason == "length"


def test_page_pool_invariants():
    pool = PagePool(n_pages=5)                # page 0 reserved
    assert pool.available == 4
    got = pool.alloc(4)
    assert sorted(got) == [1, 2, 3, 4] and pool.alloc(1) is None
    pool.release([got[0]])
    assert pool.available == 1
    with pytest.raises(AssertionError):
        pool.release([0])                     # trash page is never released


# ============================================ scheduler state round-trip

def test_scheduler_state_roundtrip_mid_flight(cfg, params):
    """state_tree -> load_state into a fresh scheduler reproduces the
    exact remaining token stream (pool bits, tables, session metadata and
    the queued/running split all survive)."""
    geom = dict(page_size=4, n_pages=6, max_slots=2, max_seq_len=32)
    a = DecodeScheduler(cfg, params, **geom)
    sids = [a.submit([s, s + 1], max_new_tokens=10) for s in (1, 5, 9)]
    for _ in range(4):
        a.step()
    tree = jax.tree.map(np.copy, a.state_tree())

    b = DecodeScheduler(cfg, params, init_pool=False, **geom)
    b.load_state(tree)
    assert {s: b.sessions[s].generated for s in b.sessions} == \
           {s: a.sessions[s].generated for s in a.sessions}
    drain(a), drain(b)
    for sid in sids:
        assert a.sessions[sid].generated == b.sessions[sid].generated
        assert b.sessions[sid].state == "done"


def test_abstract_state_matches_concrete(cfg, params):
    geom = dict(page_size=4, n_pages=6, max_slots=2, max_seq_len=32)
    sch = DecodeScheduler(cfg, params, **geom)
    sch.submit([1, 2, 3], max_new_tokens=4)
    sch.step()
    concrete = sch.state_tree()
    abstract = DecodeScheduler.abstract_state(cfg, **geom)
    cl = jax.tree.leaves(concrete)
    al = jax.tree.leaves(abstract)
    assert len(cl) == len(al)
    for c, ab in zip(cl, al):
        assert tuple(np.shape(c)) == tuple(ab.shape)
        assert np.asarray(c).dtype == ab.dtype


# ===================================== runtime + daemon event plumbing

def make_daemon(tmp_path, **kw):
    from repro.core.daemon import ClusterDaemon
    from repro.core.topology import Topology
    topo = Topology(n_pods=1, pod_x=2, pod_y=1)
    dev = jax.devices()[0]
    return ClusterDaemon(topo, devices=[dev] * topo.n_chips,
                         ckpt_root=str(tmp_path / "ckpt"), **kw)


def paged_job(cfg, **kw):
    from repro.core.runtime import JobSpec
    shape = ShapeConfig("s", "serve", seq_len=32, global_batch=1)
    geom = dict(paged=True, page_size=4, max_slots=4)
    geom.update(kw)
    return JobSpec(cfg, shape, kind="serve", **geom)


@pytest.mark.slow
def test_runtime_session_api_and_event_kinds(tmp_path, cfg):
    """start_session -> engine-driven decode -> harvested emissions surface
    on the bus as ``generate``/``session`` events, in order, and the
    engine quiesces (idle_serve) once the session finishes."""
    d = make_daemon(tmp_path)
    app, _ = d.submit("alice", "serve", 1, job=paged_job(cfg))
    rt = d.runtime(app)
    assert rt.idle_serve                      # no sessions yet
    sid = d.generate(app, [7, 8, 9], max_new_tokens=5)
    d.autostep_enable(app)
    for i in range(12):
        d.autostep_round(now=1.0 + i)
    evs = [e for e in d.events_since(0)
           if e.kind in ("generate", "session")
           and e.payload.get("session") == sid]
    gen = [e for e in evs if e.kind == "generate"]
    assert [e.payload["index"] for e in gen] == list(range(5))
    assert [e.payload["done"] for e in gen] == [False] * 4 + [True]
    actions = [e.payload["action"] for e in evs if e.kind == "session"]
    assert actions == ["submitted", "admitted", "finished"]
    assert rt.sessions.sessions[sid].generated == \
        [e.payload["token"] for e in gen]
    assert rt.idle_serve                      # engine goes quiet again
    before = d.bus.latest_seq
    for i in range(3):
        d.autostep_round(now=20.0 + i)
    assert d.bus.latest_seq == before         # no idle step/event chatter
    # generate against a non-paged target refuses cleanly
    with pytest.raises((ValueError, KeyError)):
        d.generate("nope", [1])


@pytest.mark.slow
def test_paged_sessions_survive_preempt_resume(tmp_path, cfg, params):
    """An in-flight session's pool/page-table/metadata checkpoint on
    preemption and the resumed block finishes the stream bit-identically
    to an uninterrupted run."""
    d = make_daemon(tmp_path)
    app, _ = d.submit("alice", "serve", 1, job=paged_job(cfg))
    rt = d.runtime(app)
    sid = rt.start_session([7, 8, 9], max_new_tokens=10)
    rt.feed(rounds=4)
    partial = list(rt.sessions.sessions[sid].generated)
    assert 0 < len(partial) < 10

    d.preempt(app, reason="pool checkpoint test")
    assert rt.sessions is None                # suspended: state on disk only
    d.tick()                                  # auto-resume
    sess = rt.sessions.sessions[sid]
    assert sess.generated == partial          # nothing lost, nothing replayed
    while rt.sessions.has_work:
        rt.feed()
    ref_sch = DecodeScheduler(rt.job.cfg, rt.state["params"], page_size=4,
                              max_slots=4, max_seq_len=32)
    x = ref_sch.submit([7, 8, 9], max_new_tokens=10)
    drain(ref_sch)
    assert sess.state == "done"
    assert sess.generated == ref_sch.sessions[x].generated


@pytest.mark.slow
def test_cross_geometry_resume_of_active_session(tmp_path):
    """Suspend a paged serve block on a 2-chip mesh, resume on 1 chip: the
    checkpoint manager reshards params onto the new mesh and the rebuilt
    scheduler continues the session bit-identically.  Needs >1 device, so
    runs in a subprocess (dry-run isolation rule)."""
    code = f"""
    import jax, numpy as np
    import repro.configs as C
    from repro.core.controller import ClusterController
    from repro.core.runtime import JobSpec
    from repro.core.topology import Topology
    from repro.models.config import ShapeConfig
    from repro.serve.decode_scheduler import DecodeScheduler

    topo = Topology(n_pods=1, pod_x=4, pod_y=2)
    ctl = ClusterController(topo, ckpt_root={str(tmp_path)!r})
    cfg = C.get_smoke("mistral_nemo_12b")
    shape = ShapeConfig("s", "serve", seq_len=32, global_batch=1)
    job = JobSpec(cfg, shape, kind="serve", paged=True, page_size=4,
                  max_slots=4)
    a, g = ctl.submit("alice", "serve", 2, job=job)
    assert g.mesh_shape in ((1, 2), (2, 1)), g.mesh_shape
    rt = ctl.runtimes[a]
    sid = rt.start_session([5, 6, 7], max_new_tokens=10)
    rt.feed(rounds=3)
    ctl.preempt(a, "geometry test")
    grant = ctl.resume(a, n_chips=1)
    assert grant.mesh_shape == (1, 1), grant.mesh_shape
    rt = ctl.runtimes[a]
    while rt.sessions.has_work:
        rt.feed()
    toks = rt.sessions.sessions[sid].generated
    sch = DecodeScheduler(cfg, rt.state["params"], page_size=4,
                          max_slots=4, max_seq_len=32)
    x = sch.submit([5, 6, 7], max_new_tokens=10)
    while sch.has_work:
        sch.step()
    assert toks == sch.sessions[x].generated, (toks,
                                               sch.sessions[x].generated)
    ctl.partitioner.check_invariants()
    print("SERVE_GEOMETRY_OK")
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    assert "SERVE_GEOMETRY_OK" in r.stdout


# ================================================= generate over the wire

SERVE_JOB = {"kind": "serve", "arch": "mistral_nemo_12b", "paged": True,
             "page_size": 4, "max_slots": 4, "seq_len": 32,
             "global_batch": 1}


@pytest.fixture
def gw(tmp_path):
    from repro.gateway import GatewayServer, ProfileStore, UserProfile
    daemon = make_daemon(tmp_path, background=True, tick_interval_s=0.01)
    profiles = ProfileStore([UserProfile("alice", "tok-alice"),
                             UserProfile("bob", "tok-bob")])
    server = GatewayServer(daemon, profiles).start()
    yield server, daemon
    server.stop()
    daemon.stop()


def req(server, method, path, token=None, body=None, timeout=30):
    r = urllib.request.Request(server.url + path, method=method,
                               data=(json.dumps(body).encode()
                                     if body is not None else None))
    if token:
        r.add_header("Authorization", f"Bearer {token}")
    try:
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def submit_paged(server, token="tok-alice"):
    s, a = req(server, "POST", "/v1/submit", token,
               {"job_description": "serve", "n_chips": 1,
                "job": SERVE_JOB})
    assert s == 201 and a["admitted"], a
    return a["app_id"]


@pytest.mark.slow
def test_generate_sse_stream_over_the_wire(gw):
    """The quickstart path: submit a paged serve block, POST a prompt,
    read the token-by-token SSE stream to the final frame."""
    server, daemon = gw
    app = submit_paged(server)
    r = urllib.request.Request(
        server.url + f"/v1/blocks/{app}/generate", method="POST",
        data=json.dumps({"prompt": [5, 6, 7],
                         "max_new_tokens": 6}).encode())
    r.add_header("Authorization", "Bearer tok-alice")
    frames = []
    with urllib.request.urlopen(r, timeout=30) as resp:
        assert resp.headers["Content-Type"].startswith("text/event-stream")
        cur = {}
        for raw in resp:
            line = raw.decode().rstrip("\n")
            if line.startswith("event: "):
                cur["event"] = line[7:]
            elif line.startswith("data: "):
                cur["data"] = json.loads(line[6:])
            elif line == "" and cur.get("data"):
                frames.append(cur)
                cur = {}
    gen = [f for f in frames if f["event"] == "generate"]
    assert [f["data"]["index"] for f in gen] == list(range(6))
    assert gen[-1]["data"]["done"] is True
    acts = [f["data"]["action"] for f in frames if f["event"] == "session"]
    assert acts[0] == "submitted" and "admitted" in acts
    # the streamed tokens are the session's actual output
    rt = daemon.runtime(app)
    sid = gen[0]["data"]["session"]
    assert [f["data"]["token"] for f in gen] == \
        rt.sessions.sessions[sid].generated
    req(server, "POST", f"/v1/blocks/{app}/expire", "tok-alice", {})


@pytest.mark.slow
def test_generate_longpoll_validation_and_ownership(gw):
    server, daemon = gw
    app = submit_paged(server)
    s, out = req(server, "POST", f"/v1/blocks/{app}/generate", "tok-alice",
                 {"prompt": [9, 9], "max_new_tokens": 4, "stream": False})
    assert s == 200 and out["done"] and len(out["tokens"]) == 4
    # two concurrent sessions keep their streams apart
    s2, out2 = req(server, "POST", f"/v1/blocks/{app}/generate",
                   "tok-alice", {"prompt": [1, 2, 3], "max_new_tokens": 4,
                                 "stream": False})
    assert s2 == 200 and out2["session"] != out["session"]
    # malformed prompts never reach the scheduler
    for bad in [None, [], [1.5], [-1], [True], "abc"]:
        s, e = req(server, "POST", f"/v1/blocks/{app}/generate",
                   "tok-alice", {"prompt": bad, "stream": False})
        assert s == 400, bad
    s, _ = req(server, "POST", f"/v1/blocks/{app}/generate", "tok-alice",
               {"prompt": [1], "max_new_tokens": 0, "stream": False})
    assert s == 400
    # ownership: bob cannot generate on alice's block
    s, _ = req(server, "POST", f"/v1/blocks/{app}/generate", "tok-bob",
               {"prompt": [1], "stream": False})
    assert s == 403
    # a dense (non-paged) serve block has no generate surface -> 409
    s, dense = req(server, "POST", "/v1/submit", "tok-bob",
                   {"job_description": "dense", "n_chips": 1,
                    "job": {"kind": "serve", "arch": "mistral_nemo_12b",
                            "seq_len": 32, "global_batch": 1}})
    assert s == 201
    s, e = req(server, "POST",
               f"/v1/blocks/{dense['app_id']}/generate", "tok-bob",
               {"prompt": [1], "stream": False})
    assert s == 409 and "paged" in e["error"]
    for a, t in [(app, "tok-alice"), (dense["app_id"], "tok-bob")]:
        req(server, "POST", f"/v1/blocks/{a}/expire", t, {})


@pytest.mark.slow
def test_generate_storm_429_and_body_cap_413(tmp_path):
    """Satellite hardening: the generate endpoint sits behind the same
    per-session token bucket (429 on a storm) and body cap (413 on an
    oversized prompt) as every other authed route."""
    from repro.gateway import GatewayServer, ProfileStore, UserProfile
    daemon = make_daemon(tmp_path, background=True, tick_interval_s=0.01)
    profiles = ProfileStore([UserProfile("alice", "tok-alice"),
                             UserProfile("bob", "tok-bob")])
    server = GatewayServer(daemon, profiles, rate_limit_rps=0.001,
                           rate_limit_burst=4, max_body_bytes=2048).start()
    try:
        cfg = tiny_cfg()
        app = submit_paged(server)            # burst 1
        gen = f"/v1/blocks/{app}/generate"
        body = {"prompt": [1, 2], "max_new_tokens": 2, "stream": False}
        codes = [req(server, "POST", gen, "tok-alice", body)[0]
                 for _ in range(6)]
        assert codes[:3] == [200, 200, 200], codes    # burst 2..4
        assert codes[3:] == [429, 429, 429], codes    # storm throttled
        s, e = req(server, "POST", gen, "tok-alice", body)
        assert s == 429 and e["retry_after_s"] > 0
        # another user's bucket is untouched by alice's storm
        app_b = submit_paged(server, "tok-bob")
        s, _ = req(server, "POST", f"/v1/blocks/{app_b}/generate",
                   "tok-bob", {"prompt": [3], "max_new_tokens": 2,
                               "stream": False})
        assert s == 200
        # oversized prompt body: refused by the cap before parsing (the
        # server may close the socket without reading the body)
        try:
            s, e = req(server, "POST", f"/v1/blocks/{app_b}/generate",
                       "tok-bob", {"prompt": list(range(1000)),
                                   "stream": False})
            assert s == 413 and "cap" in e["error"]
        except (ConnectionError, urllib.error.URLError):
            pass
        assert req(server, "GET", "/v1/ping")[0] == 200   # still serving
    finally:
        server.stop()
        daemon.stop()
