"""The comparison that decides ``correct``: what the timed path produced
against the plain reference, number by number, each against its limit.

Train: the first three steps of the very block the window then drives.
Each step's loss and the first step's pre-clip gradient norm (relative
gaps, the worst over the steps), the first clipped gradient as the
optimizer holds it (its first moment over 1 - b1) and the parameters'
change after three steps, each by its worst leaf:
| |x_prog| - |x_ref| | / max(|x_ref|, median leaf |x_ref|); and, since
norms average a lower precision's rounding away, the first gradient's
direction by its worst leaf, 1 - cos(g_prog, g_ref).  Leaves whose
reference gradient is under a thousandth of the median leaf's are left
out of the leaf numbers.  Where the configuration names a
``direction_leaf``, the first gradient's direction at that leaf,
``leaf_cos``, is compared too: a number steady from seed to seed where the
worst leaf's direction is not (the configuration says why).

Serve: for a sample of finished requests, drawn from the seed with the
longest among them, the widest gap by which a served token's reference
logit lies below the reference's best logit at that position."""
from __future__ import annotations

import numpy as np

LEAF_FLOOR = 1e-3


def _leaf_gap(prog, ref, grad_ref):
    prog, ref, grad_ref = (np.asarray(x, np.float64)
                           for x in (prog, ref, grad_ref))
    keep = grad_ref >= LEAF_FLOOR * np.median(grad_ref)
    denom = np.maximum(ref, np.median(ref[keep]))
    gaps = np.where(keep, np.abs(prog - ref) / denom, 0.0)
    worst = int(np.argmax(gaps))
    return float(gaps[worst]), worst, int((~keep).sum())


def cos_gaps(a_leaves, b_leaves):
    """Per leaf, 1 - cos(a, b): how far the two gradients point apart."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gap(a, b):
        a = a.astype(jnp.float32).ravel()
        b = b.astype(jnp.float32).ravel()
        hi = jax.lax.Precision.HIGHEST
        return 1.0 - jnp.dot(a, b, precision=hi) / jnp.sqrt(
            jnp.dot(a, a, precision=hi) * jnp.dot(b, b, precision=hi))

    return [float(gap(jnp.asarray(a), jnp.asarray(b)))
            for a, b in zip(a_leaves, b_leaves)]


def train_numbers(prog, ref, direction_leaf=None):
    """prog/ref: dicts with losses[3], grad_norms[3], grad_leaf[n],
    change_leaf[n] and grad_first (the first clipped gradient's leaves);
    ref also grad_raw_leaf[n] (the unclipped reference gradient's leaf
    norms) and leaf_names[n].  Returns {name: value} and notes."""
    rel = lambda a, b: float(np.max(np.abs(np.asarray(a) - np.asarray(b))
                                    / np.abs(np.asarray(b))))
    g, gi, n_out = _leaf_gap(prog["grad_leaf"], ref["grad_leaf"],
                             ref["grad_raw_leaf"])
    c, ci, _ = _leaf_gap(prog["change_leaf"], ref["change_leaf"],
                         ref["grad_raw_leaf"])
    raw = np.asarray(ref["grad_raw_leaf"], np.float64)
    keep = raw >= LEAF_FLOOR * np.median(raw)
    cos_leaf = cos_gaps(prog["grad_first"], ref["grad_first"])
    cos = np.where(keep, cos_leaf, 0.0)
    di = int(np.argmax(cos))
    nums = {"loss": rel(prog["losses"], ref["losses"]),
            "grad_norm": rel(prog["grad_norms"][:1], ref["grad_norms"][:1]),
            "grad_leaf": g, "change_leaf": c, "grad_cos": float(cos[di])}
    names = ref.get("leaf_names") or []
    name = lambda i: names[i] if names else i
    if direction_leaf is not None:
        nums["leaf_cos"] = float(cos_leaf[names.index(direction_leaf)])
    notes = {"grad_leaf_worst": name(gi), "change_leaf_worst": name(ci),
             "grad_cos_worst": name(di), "leaves_left_out": n_out,
             "change_leaf_prog": list(map(float, prog["change_leaf"])),
             "change_leaf_ref": list(map(float, ref["change_leaf"])),
             "grad_cos_leaf": list(map(float, cos_leaf))}
    return nums, notes


def serve_numbers(gaps):
    return {"logit_gap": float(np.max(gaps)) if len(gaps) else float("inf")}


def judge(nums, limits, not_compared=()):
    """{name: {"value", "limit"}} and whether every number is within its
    limit.  A number the configuration names as not compared (it has no
    reading that separates sound runs from the control and the faults) is
    reported with no limit; any other number with no limit fails."""
    checks, ok = {}, True
    for name, value in nums.items():
        lim = limits.get(name)
        checks[name] = {"value": value, "limit": lim}
        if lim is None and name in not_compared:
            continue
        good = lim is not None and np.isfinite(value) and value <= lim
        ok = ok and bool(good)
    return checks, ok


# --------------------------------------------------------------- reference

def train_reference(cfg, mix, seed: int, prec: str = "f32", steps: int = 3,
                    rows: int = 0):
    """The reference's readings over the first ``steps`` steps from the
    seed's weights, on the seed's batches (their first ``rows`` rows only,
    where ``rows`` is set: a fault the comparison has to catch)."""
    import jax
    import jax.numpy as jnp
    from bench import data, reference
    from bench.reference import common
    model = reference.of(cfg)
    o = cfg["optimizer"]
    with jax.default_matmul_precision("highest"):
        params = model.init_params(cfg, seed)
        p0 = params
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, norms = [], []
        grad_leaf = grad_raw = None
        for step in range(steps):
            toks, labels = data.lcg_batch(seed, step, mix["global_batch"],
                                          mix["seq_len"], cfg["vocab_size"])
            if rows:
                toks, labels = toks[:rows], labels[:rows]
            loss, grads = model.loss_and_grad(params, jnp.asarray(toks),
                                              jnp.asarray(labels), cfg, prec)
            params, m, v, gnorm, clipped = common.adamw(
                params, grads, m, v, float(step + 1),
                float(common.lr_at(o, step + 1)), b1=o["b1"], b2=o["b2"],
                eps=o["eps"], wd=o["weight_decay"], clip=o["clip_norm"])
            losses.append(float(loss))
            norms.append(float(gnorm))
            if step == 0:
                grad_leaf = leaf_norms(clipped)
                grad_raw = leaf_norms(grads)
                grad_first = jax.device_get(jax.tree.leaves(clipped))
            del grads, clipped
        change = diff_norms(params, p0)
    return {"losses": losses, "grad_norms": norms, "grad_leaf": grad_leaf,
            "grad_raw_leaf": grad_raw, "change_leaf": change,
            "grad_first": grad_first, "leaf_names": leaf_names(p0)}


def leaf_names(tree):
    import jax
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def leaf_norms(tree):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])
    return [float(x) for x in f(tree)]


def diff_norms(a, b):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda s, t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - y.astype(jnp.float32))))
        for x, y in zip(jax.tree.leaves(s), jax.tree.leaves(t))])
    return [float(x) for x in f(a, b)]


#: the serve reference pads its positions and compared tokens up to these
#: steps, and every batch to its full rows, so that runs on other seeds
#: find its few programs in the compilation cache; under causal attention
#: no padding reaches a compared position
PAD_POSITIONS, PAD_TOKENS = 256, 64


def _pad(n: int, step: int) -> int:
    return -(-n // step) * step


def serve_reference(cfg, seed: int, samples, prec: str = "f32",
                    batch: int = 4):
    """Per sampled request (prompt, served tokens): the reference's gap of
    each served token; with ``prec`` below float32 (the control), the gap
    under the float32 reference of the token the control puts first."""
    import jax
    from bench import reference
    model = reference.of(cfg)
    gaps = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(samples), batch):
            part = samples[i:i + batch]
            T = _pad(max(len(p) + len(s) - 1 for p, s in part),
                     PAD_POSITIONS)
            N = _pad(max(len(s) for _, s in part), PAD_TOKENS)
            toks = np.zeros((batch, T), np.int32)
            idx = np.zeros((batch, N), np.int32)
            for b, (p, s) in enumerate(part):
                seq = list(p) + list(s[:-1])
                toks[b, :len(seq)] = seq
                idx[b, :len(s)] = np.arange(len(p) - 1, len(p) - 1 + len(s))
            ref = np.asarray(model.logits_at(cfg, seed, toks, idx, "f32"))
            if prec != "f32":
                ctl = np.asarray(model.logits_at(cfg, seed, toks, idx, prec))
            for b, (p, s) in enumerate(part):
                r = ref[b, :len(s)]
                pick = (np.asarray(s) if prec == "f32"
                        else np.argmax(ctl[b, :len(s)], -1))
                gaps.extend((r.max(-1) - r[np.arange(len(s)), pick]).tolist())
    return np.asarray(gaps)
