"""The timed path broken underneath: ``correct`` has to come out false.

One test per fault a test-size cell can have: a train step that returns
its state unchanged, half of the batch left out with the mean taken over
the rest, a decode step that leaves its cache unchanged, and a token
altered where it is produced.  The exchange between chips has no fault
here: every cell of the benchmark runs its blocks on one chip each."""
import jax

from bench.tests.harness import run_cell

TRAIN, SERVE = "xlstm_tiny.train", "llama_tiny.chat"


def test_sound_program_is_correct():
    assert run_cell(TRAIN, seed=1)["correct"] is True
    assert run_cell(SERVE, seed=1)["correct"] is True


def _patch_train_step(monkeypatch, wrap):
    from repro.train import train_step
    orig = train_step.make_train_step

    def make(*a, **k):
        return wrap(orig(*a, **k))

    monkeypatch.setattr(train_step, "make_train_step", make)


def test_step_that_keeps_its_state(monkeypatch):
    def wrap(step):
        def bad(state, batch):
            _, metrics = step(state, batch)
            return state, metrics
        return bad
    _patch_train_step(monkeypatch, wrap)
    res = run_cell(TRAIN, seed=1)
    assert res["correct"] is False
    assert res["checks"]["change_leaf"]["value"] > 0.9
    # the first moment stays nought, so the first gradient reads as gone
    assert res["checks"]["grad_leaf"]["value"] > 0.9


def test_half_of_the_batch_left_out(monkeypatch):
    def wrap(step):
        def bad(state, batch):
            return step(state, jax.tree.map(
                lambda x: x[: x.shape[0] // 2], batch))
        return bad
    _patch_train_step(monkeypatch, wrap)
    res = run_cell(TRAIN, seed=1)
    assert res["correct"] is False


def _patch_decode(monkeypatch, wrap):
    from repro.serve.decode_scheduler import DecodeScheduler
    orig = DecodeScheduler._make_decode

    def make(self):
        return wrap(self, orig(self))

    monkeypatch.setattr(DecodeScheduler, "_make_decode", make)


def test_token_altered_where_produced(monkeypatch):
    def wrap(sch, fn):
        def bad(*a, **k):
            nxt, pool = fn(*a, **k)
            return (nxt + 1) % sch.cfg.vocab_size, pool
        return bad
    _patch_decode(monkeypatch, wrap)
    res = run_cell(SERVE, seed=1)
    assert res["correct"] is False


def test_decode_that_keeps_its_cache(monkeypatch):
    def wrap(sch, fn):
        def bad(params, tokens, pool, *a, **k):
            kept = jax.tree.map(lambda x: x + 0, pool)
            nxt, _ = fn(params, tokens, pool, *a, **k)
            return nxt, kept
        return bad
    _patch_decode(monkeypatch, wrap)
    res = run_cell(SERVE, seed=1)
    assert res["correct"] is False
