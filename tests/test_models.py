"""Per-architecture smoke tests (reduced configs) + decode/prefill
consistency against the full forward pass."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.configs import xlstm_350m
from repro.data import pipeline
from repro.kernels import ops
from repro.models import model, ssm
from repro.models.config import ShapeConfig

KEY = jax.random.PRNGKey(7)
B, S = 2, 32


def make_batch(cfg, with_labels=True):
    ks = jax.random.split(KEY, 4)
    if cfg.frontend == "frame":
        b = {"frames": jax.random.normal(ks[0], (B, S, cfg.frontend_dim)),
             "mask": jax.random.bernoulli(ks[1], 0.3, (B, S))}
        if with_labels:
            b["labels"] = jax.random.randint(ks[2], (B, S), 0, cfg.vocab_size)
        return b
    if cfg.frontend == "patch":
        n_p = 4
        b = {"tokens": jax.random.randint(ks[0], (B, S - n_p), 0,
                                          cfg.vocab_size),
             "patches": jax.random.normal(ks[1], (B, n_p, cfg.frontend_dim))}
        if with_labels:
            b["labels"] = jax.random.randint(ks[2], (B, S - n_p), 0,
                                             cfg.vocab_size)
        return b
    b = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)}
    if with_labels:
        b["labels"] = jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)
    return b


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_smoke_forward_loss(arch):
    """Assigned-architecture smoke: reduced config, one loss eval, finite."""
    cfg = C.get_smoke(arch)
    params = model.init_params(cfg, KEY)
    loss, metrics = jax.jit(
        lambda p, b: model.loss_fn(p, cfg, b))(params, make_batch(cfg))
    assert np.isfinite(float(loss)), (arch, loss)
    # random-init loss should be near ln(vocab)
    assert float(loss) < np.log(cfg.vocab_size) + 2.0


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_smoke_train_step(arch):
    """One full train step: grads flow, params update, loss finite."""
    from repro.train import optimizer as opt_lib
    from repro.train import train_step as train_lib
    cfg = C.get_smoke(arch)
    shape = ShapeConfig("t", "train", seq_len=S, global_batch=B, microbatch=1)
    opt_cfg = opt_lib.OptConfig(warmup_steps=1, total_steps=4)
    state = train_lib.make_train_state(cfg, KEY, opt_cfg)
    step = jax.jit(train_lib.make_train_step(cfg, shape, opt_cfg))
    p0 = jax.tree.map(lambda x: np.asarray(x, np.float32), state["params"])
    state, metrics = step(state, make_batch(cfg))
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    changed = jax.tree.map(
        lambda a, b: not np.allclose(a, np.asarray(b, np.float32)), p0,
        state["params"])
    assert any(jax.tree.leaves(changed)), f"{arch}: no param moved"


@pytest.mark.parametrize("arch", [a for a in C.ARCH_IDS
                                  if not C.get(a).is_encoder])
def test_decode_consistency(arch):
    """prefill + decode token-by-token == one full causal forward pass."""
    cfg = C.get_smoke(arch).replace(param_dtype="float32")
    if cfg.moe is not None:
        # decode routes per-step with tiny per-call capacity; boost capacity
        # so no tokens drop and the math is exactly comparable.  f32 params
        # keep top-k routing decisions stable between the two paths (bf16
        # wobble can flip an expert choice, which is a discontinuity).
        cfg = cfg.replace(moe=dataclasses.replace(cfg.moe,
                                                  capacity_factor=16.0))
    params = model.init_params(cfg, KEY)
    batch = make_batch(cfg, with_labels=False)
    smax = S + 4

    # full forward logits
    x = model.embed_inputs(params, cfg, batch)
    full_logits, _, _ = jax.jit(
        lambda p, xx: model.forward(p, cfg, xx,
                                    positions=jnp.arange(xx.shape[1]))
    )(params, x)

    # prefill over the first P positions, then decode the rest
    P = S - 3
    if cfg.frontend == "patch":
        pf_batch = {"tokens": batch["tokens"][:, :P - 4],
                    "patches": batch["patches"]}
        tail_tokens = batch["tokens"][:, P - 4:]
    else:
        pf_batch = {"tokens": batch["tokens"][:, :P]}
        tail_tokens = batch["tokens"][:, P:]
    cache = model.init_cache(cfg, B, smax)
    logits_last, cache = jax.jit(
        lambda p, b, c: model.prefill(p, cfg, b, c))(params, pf_batch, cache)
    np.testing.assert_allclose(
        np.asarray(logits_last, np.float32),
        np.asarray(full_logits[:, P - 1], np.float32), atol=3e-2, rtol=3e-2)

    dec = jax.jit(lambda p, t, c, l: model.decode_step(p, cfg, t, c, l))
    for i in range(tail_tokens.shape[1]):
        tok = tail_tokens[:, i:i + 1]
        logits, cache = dec(params, tok, cache, jnp.int32(P + i))
        np.testing.assert_allclose(
            np.asarray(logits, np.float32),
            np.asarray(full_logits[:, P + i], np.float32),
            atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_input_specs_cover_cells(arch):
    """input_specs produces specs for every executed cell of this arch."""
    cfg = C.get(arch)
    for shape_name in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
        status = C.cell_status(arch, shape_name)
        if status != "run":
            assert "skip" in status
            continue
        shape = C.shape(shape_name)
        if shape.kind in ("train", "prefill"):
            specs = pipeline.input_specs(cfg, shape)
            assert specs, (arch, shape_name)
            for v in specs.values():
                assert v.shape[0] == shape.global_batch


def test_param_counts_match_published():
    expected = {  # billions, tolerance 15%
        "llama4_maverick_400b": 400, "deepseek_v2_236b": 236,
        "starcoder2_15b": 15, "deepseek_7b": 7, "mistral_nemo_12b": 12,
        "yi_34b": 34, "pixtral_12b": 12, "hubert_xlarge": 1.0,
        "zamba2_2p7b": 2.7, "xlstm_350m": 0.35,
    }
    for arch, want_b in expected.items():
        n = model.count_params(model.abstract_params(C.get(arch))) / 1e9
        assert abs(n - want_b) / want_b < 0.4, (arch, n, want_b)


# ------------------------------------------------- sLSTM chunked recurrence

def _slstm_per_step(p, x, cfg, d_model, state=None):
    """The sLSTM recurrence one time step per scan trip, over the whole
    sequence: the reference for ``ssm.slstm_fwd``'s chunked loop."""
    B, S, _ = x.shape
    H = cfg.n_heads
    Dh = d_model // H
    gates_x = (x @ p["w_gates"]).reshape(B, S, 4, H, Dh)
    if state is None:
        z = jnp.zeros((B, H, Dh), jnp.float32)
        state = {"slstm": (z, z, jnp.ones((B, H, Dh), jnp.float32), z)}
    r = p["r_gates"].astype(jnp.float32)

    def step(carry, gx):
        h, c, n, m = carry
        rec = jnp.einsum("bhd,hdg->bhg", h, r).reshape(B, H, 4, Dh)
        g = gx.astype(jnp.float32) + jnp.moveaxis(rec, 2, 1)
        z_t = jnp.tanh(g[:, 0])
        i_t = g[:, 1]
        f_t = g[:, 2]
        o_t = jax.nn.sigmoid(g[:, 3])
        logf = jax.nn.log_sigmoid(f_t)
        m_new = jnp.maximum(logf + m, i_t)
        i_p = jnp.exp(i_t - m_new)
        f_p = jnp.exp(logf + m - m_new)
        c = f_p * c + i_p * z_t
        n = f_p * n + i_p
        h = o_t * c / jnp.maximum(jnp.abs(n), 1.0)
        return (h, c, n, m_new), h.astype(jnp.bfloat16)

    final, hs = jax.lax.scan(step, tuple(state["slstm"]),
                             jnp.moveaxis(gates_x, 1, 0))
    y = jnp.moveaxis(hs, 0, 1).reshape(B, S, d_model).astype(x.dtype)
    y = ops.rmsnorm(y, p["out_norm"])
    ff = jax.nn.silu(y @ p["w_ff_gate"]) * (y @ p["w_ff_up"])
    return ff @ p["w_ff_down"], {"slstm": final}


def _slstm_case(S, with_state):
    cfg = xlstm_350m.smoke_config()
    d = cfg.d_model
    H, Dh = cfg.xlstm.n_heads, d // cfg.xlstm.n_heads
    ks = jax.random.split(jax.random.PRNGKey(S * 2 + with_state), 8)
    p = ssm.slstm_init(ks[0], d, cfg.xlstm, jnp.float32)
    x = jax.random.normal(ks[1], (2, S, d), jnp.float32)
    state = None
    if with_state:
        sh = (2, H, Dh)
        state = {"slstm": (jax.random.normal(ks[2], sh) * 0.5,
                           jax.random.normal(ks[3], sh),
                           1.0 + jax.random.uniform(ks[4], sh),
                           jax.random.normal(ks[5], sh))}
    ct = jax.random.normal(ks[6], (2, S, d), jnp.float32)
    return cfg, p, x, state, ct


@pytest.mark.parametrize("with_state", [False, True],
                         ids=["zero_state", "with_state"])
@pytest.mark.parametrize("S", [1, 15, 16, 37, 64])
def test_slstm_chunked_matches_per_step(S, with_state):
    """Outputs, final (h, c, n, m) and the gradients with respect to x,
    w_gates and r_gates match the one-step-per-trip recurrence."""
    cfg, p, x, state, ct = _slstm_case(S, with_state)
    d = cfg.d_model

    def run(fwd):
        def loss(p, x):
            y, st = fwd(p, x, cfg.xlstm, d, state=state)
            return (jnp.sum(y * ct)
                    + sum(jnp.sum(s * (i + 1)) for i, s in
                          enumerate(st["slstm"]))), (y, st["slstm"])
        (_, (y, st)), g = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(p, x)
        return y, st, g

    y, st, (gp, gx) = run(ssm.slstm_fwd)
    y_ref, st_ref, (gp_ref, gx_ref) = run(_slstm_per_step)
    tol = dict(rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(y, y_ref, **tol)
    for a, b in zip(st, st_ref):
        np.testing.assert_allclose(a, b, **tol)
    np.testing.assert_allclose(gx, gx_ref, **tol)
    for k in ("w_gates", "r_gates"):
        np.testing.assert_allclose(gp[k], gp_ref[k], **tol)


def _scan_lengths(fn, *args):
    """The lengths of the scans in ``fn``'s jaxpr, outermost first, each
    with the lengths of the scans nested in its body."""
    def walk(jaxpr):
        out = []
        for e in jaxpr.eqns:
            if e.primitive.name == "scan":
                out.append((e.params["length"],
                            walk(e.params["jaxpr"].jaxpr)))
            else:
                for sub in jax.core.jaxprs_in_params(e.params):
                    out += walk(sub)
        return out
    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("S,want", [
    (2048, [(128, [])]),            # 128 chunks of 16, no per-step loop
    (37, [(2, []), (5, [])]),       # 2 chunks, then a 5-step tail
    (15, [(15, [])]),               # a short prefill: one step a trip
    (1, [(1, [])]),                 # decode: the single step
])
def test_slstm_loop_trips_follow_the_sequence_length(S, want):
    """At the benchmark's widths the sLSTM forward loops S // 16 chunked
    trips with no loop inside them, then S mod 16 single steps."""
    cfg = xlstm_350m.CONFIG
    d = cfg.d_model
    p = jax.eval_shape(lambda: ssm.slstm_init(jax.random.PRNGKey(0), d,
                                              cfg.xlstm, jnp.bfloat16))
    x = jax.ShapeDtypeStruct((2, S, d), jnp.bfloat16)
    state = (None if S > 1 else jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(*s),
        ssm.slstm_state_spec(cfg.xlstm, d, 2),
        is_leaf=lambda s: isinstance(s, tuple) and isinstance(s[1], type)))
    got = _scan_lengths(
        lambda p, x, st: ssm.slstm_fwd(p, x, cfg.xlstm, d, state=st),
        p, x, state)
    assert got == want
