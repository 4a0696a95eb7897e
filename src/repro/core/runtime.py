"""BlockRuntime — the per-tenant execution engine (the paper's "MPD ring").

Activating a block builds its private sub-mesh over the admin-assigned
devices, compiles the job's step function with the block's parallelism plan,
and installs sharded state.  Each block's runtime is fully independent of
every other block's (separate mesh, separate compiled executables, separate
checkpoint namespace) — the multi-daemon isolation property of the paper.

Preemption support: ``suspend()`` drains the in-flight window, writes a
synchronous checkpoint and drops every device reference, so the chips can
be re-granted to another block; ``resume(grant, devices)`` rebuilds the
runtime on a possibly *different* chip set / mesh geometry and restores the
suspended state from the checkpoint (host leaves are resharded onto the new
mesh by the checkpoint manager).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.core.block import BlockGrant
from repro.core.inflight import InflightWindow
from repro.data import pipeline
from repro.models import model as model_lib
from repro.models.config import ModelConfig, ShapeConfig
from repro.obs.programs import PROGRAMS
from repro.serve import serve_step as serve_lib
from repro.sharding import ctx as shard_ctx
from repro.sharding import plans
from repro.train import compile_cache
from repro.train import optimizer as opt_lib
from repro.train import train_step as train_lib


@dataclasses.dataclass
class JobSpec:
    cfg: ModelConfig
    shape: ShapeConfig
    kind: str = "train"              # train | serve
    opt: opt_lib.OptConfig = dataclasses.field(default_factory=opt_lib.OptConfig)
    seed: int = 0
    decode_sample: bool = False      # serve: sample instead of greedy argmax
    collect_metrics: bool = False    # carry step metrics (loss, grad_norm)
                                     # through the async window into each
                                     # completion record (extra host
                                     # transfers per step — drivers that
                                     # log per-step opt in)
    ckpt_namespace: Optional[str] = None  # stable checkpoint namespace so a
                                          # relaunched driver can --resume;
                                          # default: the (random) block id
    ckpt_every: int = 0              # periodic checkpoint interval under
                                     # daemon-side autostep (client-driven
                                     # drivers call save() between batches
                                     # themselves; the engine reads this)
    # ---- serve: continuous batching over a paged KV cache ----
    paged: bool = False              # serve: slot-batched generate sessions
                                     # over a shared page pool instead of the
                                     # single dense prefill/decode context
    page_size: int = 16              # rows per KV page
    n_pages: int = 0                 # pool size; 0 derives full residency
                                     # (max_slots * pages_per_seq + trash)
    max_slots: int = 8               # concurrent decode batch width
    max_seq_len: int = 0             # per-session context cap; 0 -> shape.seq_len


@dataclasses.dataclass
class SimJobSpec:
    """Device-free stand-in for a JobSpec: activating a block with one
    boots a ``scheduler.SimRuntime`` (wall-clock step model with the full
    suspend/resume preemption surface) instead of compiling a real
    runtime.  The web gateway's ``{"kind": "sim"}`` jobs, the gateway
    tests and the throughput benchmarks drive the identical lifecycle —
    admission, dispatch, preemption, expiry — without XLA in the loop."""
    step_s: float = 0.001
    ckpt_every: int = 0


class BlockRuntime(InflightWindow):
    def __init__(self, grant: BlockGrant, job: JobSpec,
                 devices: Sequence[jax.Device], ckpt_root: str,
                 app_id: Optional[str] = None):
        self.job = job
        self.app_id = app_id         # names the train step in obs PROGRAMS
        self.ckpt = CheckpointManager(
            ckpt_root, namespace=job.ckpt_namespace or grant.block_id)
        self.state: Any = None
        self.cache: Any = None
        self.sessions = None         # paged serve: the DecodeScheduler
        self._emissions: list = []   # paged serve: buffered generate events
        self.step_count = 0
        self.last_saved_step = 0     # step_count at the last checkpoint
        self.suspended = False
        self._init_window()
        self._attach(grant, devices)

    def _attach(self, grant: BlockGrant,
                devices: Sequence[jax.Device]) -> None:
        """Bind to a chip set: build the sub-mesh and (re)compile the step
        function.  Called at activation and again on resume-after-preemption
        (possibly with different chips / a different mesh geometry)."""
        assert len(devices) == int(np.prod(grant.mesh_shape)), (
            len(devices), grant.mesh_shape)
        self.grant = grant
        self.devices = list(devices)
        self.mesh = Mesh(np.asarray(self.devices).reshape(grant.mesh_shape),
                         ("data", "model"))
        self.axes = plans.MeshAxes(dp=("data",), model="model")
        self.ctx = shard_ctx.ShardCtx(self.mesh, ("data",), "model")
        # arrays the block makes outside its step (caches, page pool,
        # decode inputs) live replicated on the block's own chips
        self.replicated = NamedSharding(self.mesh, P())
        self._build()

    # ------------------------------------------------------------ compile
    def _cache_key(self, family: str, *extra) -> tuple:
        """Logical build signature: everything the jitted step's trace can
        depend on.  ``seed``/checkpoint fields deliberately excluded — they
        never reach the compiled computation."""
        job = self.job
        return (family, compile_cache.freeze(job.cfg),
                compile_cache.freeze(job.shape),
                compile_cache.mesh_fingerprint(self.mesh)) + extra

    def _cached(self, key, builder, label: str):
        return compile_cache.GLOBAL.get(
            key, builder, label=label, block_id=self.grant.block_id)

    def _build(self) -> None:
        job = self.job
        if job.kind == "train":
            state_abs = train_lib.abstract_train_state(job.cfg, job.opt)
            p_spec = plans.param_specs(state_abs["params"], self.mesh, self.axes)
            state_spec = {"params": p_spec,
                          "opt": plans.opt_state_specs(state_abs["opt"], p_spec)}
            self.state_shardings = plans.to_shardings(state_spec, self.mesh)
            batch_abs = pipeline.input_specs(job.cfg, job.shape)
            b_spec = plans.batch_specs(batch_abs, self.mesh, self.axes)
            self.batch_shardings = plans.to_shardings(b_spec, self.mesh)
            jit_kw = dict(in_shardings=(self.state_shardings,
                                        self.batch_shardings),
                          out_shardings=(self.state_shardings, None),
                          donate_argnums=(0,))

            def build_train():
                # everything the closure captures (ctx, shardings) is a
                # pure function of the cache key, so a rebuild with the
                # same key can adopt this wrapper — and jax's own jit
                # cache makes re-attach on the same chips recompile-free
                step = train_lib.make_train_step(job.cfg, job.shape, job.opt)
                ctx = self.ctx

                def fn(state, batch):
                    with shard_ctx.use(ctx):
                        return step(state, batch)

                return jax.jit(fn, **jit_kw)

            self._step = self._cached(
                self._cache_key("train_step", compile_cache.freeze(job.opt),
                                ("donate", 0)),
                build_train, "train_step")
            if self.app_id is not None:
                PROGRAMS.register(self.app_id, self._step.__wrapped__,
                                  (state_abs, batch_abs), **jit_kw)
            self.data = pipeline.DataIterator(job.cfg, job.shape,
                                              seed=job.seed,
                                              shardings=self.batch_shardings)
        else:
            params_abs = model_lib.abstract_params(job.cfg)
            p_spec = plans.param_specs(params_abs, self.mesh, self.axes)
            self.state_shardings = {"params": plans.to_shardings(p_spec,
                                                                 self.mesh)}
            if job.paged:
                # the DecodeScheduler owns its own jitted prefill/decode;
                # built in init_state (it needs the params) or on restore
                self._step = None
                self._prefill_fn = None
                self._rng = jax.random.PRNGKey(job.seed + 1)
                return
            def build_decode():
                dec = serve_lib.make_decode_step(job.cfg,
                                                 sample=job.decode_sample)
                ctx = self.ctx

                if job.decode_sample:
                    def fn(params, token, cache, cache_len, key):
                        with shard_ctx.use(ctx):
                            return dec(params, token, cache, cache_len, key)
                else:
                    def fn(params, token, cache, cache_len):
                        with shard_ctx.use(ctx):
                            return dec(params, token, cache, cache_len)

                return jax.jit(fn, donate_argnums=(2,))

            self._step = self._cached(
                self._cache_key("decode_step", job.decode_sample,
                                ("donate", 2)),
                build_decode, "decode_step")
            self._prefill_fn = None   # compiled lazily on first prefill()
            self._rng = jax.random.PRNGKey(job.seed + 1)

    # --------------------------------------------------------------- state
    def init_state(self) -> None:
        job = self.job
        key = jax.random.PRNGKey(job.seed)
        if job.kind == "train":
            init = jax.jit(
                lambda k: train_lib.make_train_state(job.cfg, k, job.opt),
                out_shardings=self.state_shardings)
            self.state = init(key)
        else:
            params = jax.jit(
                lambda k: model_lib.init_params(job.cfg, k),
                out_shardings=self.state_shardings["params"])(key)
            self.state = {"params": params}
            if job.paged:
                self.sessions = self._make_scheduler(params)
                self.token = self.sessions.last_tokens_dev
                return
            B, S = job.shape.global_batch, job.shape.seq_len
            self.cache = jax.jit(
                lambda: model_lib.init_cache(job.cfg, B, S),
                out_shardings=self.replicated)()
            self.cache_len = jax.device_put(np.int32(0), self.replicated)
            self.token = jax.device_put(np.zeros((B, 1), np.int32),
                                        self.replicated)

    def _paged_geometry(self) -> Dict[str, int]:
        job = self.job
        return dict(page_size=job.page_size, n_pages=job.n_pages,
                    max_slots=job.max_slots,
                    max_seq_len=job.max_seq_len or job.shape.seq_len)

    def _make_scheduler(self, params, init_pool: bool = True):
        from repro.serve.decode_scheduler import DecodeScheduler
        job = self.job
        return DecodeScheduler(job.cfg, params, sample=job.decode_sample,
                               seed=job.seed, init_pool=init_pool,
                               sharding=self.replicated,
                               **self._paged_geometry())

    def prefill(self, batch: Dict[str, Any]) -> None:
        """Serve blocks: process a prompt batch into the KV cache and seed
        the decode loop with the first generated token (the batched-prefill
        half of the serving driver, run on the block's own sub-mesh).  The
        prefill executable is compiled lazily — resume-after-preemption
        restores the decode context from the checkpoint and never needs
        it."""
        assert self.job.kind == "serve", "prefill is a serve-block op"
        if self._prefill_fn is None:
            def build_prefill():
                pf = serve_lib.make_prefill_step(self.job.cfg)
                ctx = self.ctx

                def fn(params, batch, cache):
                    with shard_ctx.use(ctx):
                        return pf(params, batch, cache)

                return jax.jit(fn)

            self._prefill_fn = self._cached(
                self._cache_key("prefill_step"), build_prefill,
                "prefill_step")
        logits, self.cache = self._prefill_fn(self.state["params"], batch,
                                              self.cache)
        self.token = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        self.cache_len = jax.device_put(np.int32(batch["tokens"].shape[1]),
                                        self.replicated)

    # ------------------------------------------------- generate sessions
    # (paged serve only: the continuous-batching session surface the
    # daemon's "generate" command and the autostep engine drive)
    def start_session(self, prompt: Sequence[int], max_new_tokens: int = 16,
                      eos_id: Optional[int] = None) -> str:
        """Queue a generate session; tokens are emitted by subsequent decode
        steps and drained with ``harvest()`` (engine-driven) or returned
        directly by ``feed()`` (client-driven)."""
        if self.sessions is None:
            raise ValueError("block has no generate surface "
                             "(needs a paged serve job)")
        return self.sessions.submit(prompt, max_new_tokens=max_new_tokens,
                                    eos_id=eos_id)

    def feed(self, rounds: int = 1) -> list:
        """Client-driven decode: run ``rounds`` continuous-batching steps
        synchronously and return their emissions (buffered ones first)."""
        assert self.sessions is not None, "feed() needs a paged serve job"
        out = self.harvest()
        for _ in range(rounds):
            out.extend(self.sessions.step())
            self.step_count += 1
        return out

    def harvest(self) -> list:
        """Drain emissions buffered by engine-dispatched decode steps."""
        out, self._emissions = self._emissions, []
        return out

    @property
    def idle_serve(self) -> bool:
        """True when engine-dispatched steps would be no-ops (paged serve
        with no active or queued session) — the autostep engine skips
        dispatching to keep the step/event stream quiet until a generate
        arrives."""
        return self.sessions is not None and not self.sessions.has_work

    # ---------------------------------------------------------------- step
    def _decode_once(self):
        if self.job.paged:
            self._emissions.extend(self.sessions.step())
            self.token = self.sessions.last_tokens_dev
            return
        if self.job.decode_sample:
            self._rng, key = jax.random.split(self._rng)
            self.token, self.cache = self._step(self.state["params"],
                                                self.token, self.cache,
                                                self.cache_len, key)
        else:
            self.token, self.cache = self._step(self.state["params"],
                                                self.token, self.cache,
                                                self.cache_len)
        self.cache_len = self.cache_len + 1

    def step(self) -> Dict[str, float]:
        t0 = time.perf_counter()
        if self.job.kind == "train":
            batch = self.data.batch(self.step_count)
            self.state, metrics = self._step(self.state, batch)
            metrics = {k: float(v) for k, v in metrics.items()}
        else:
            self._decode_once()
            metrics = {}
        jax.block_until_ready(jax.tree.leaves(self.state)[0])
        self.step_count += 1
        metrics["step_s"] = time.perf_counter() - t0
        return metrics

    def step_async(self):
        """Dispatch one step without blocking (async dispatch overlap across
        blocks on the shared host — the paper's shared-master execution)."""
        if self.job.kind == "train":
            batch = self.data.batch(self.step_count)
            self.state, metrics = self._step(self.state, batch)
        else:
            self._decode_once()
            metrics = {}
        self.step_count += 1
        return metrics

    # ------------------------------------------------- in-flight dispatch
    # window bookkeeping (dispatch/poll/drain/inflight_depth) lives in
    # InflightWindow; a step's completion token is a device array whose
    # readiness signals the whole step finished
    def _launch(self):
        metrics = self.step_async()
        # the completion token must be an output the *next* dispatch cannot
        # donate away: the train state is donated (argnums=0), so a state
        # leaf from step N is deleted the moment step N+1 dispatches and
        # its readiness can no longer be polled at window depth >= 2.  The
        # metrics scalars (and the decode token) are plain outputs of the
        # same executable — ready exactly when the step is.
        token = (jax.tree.leaves(metrics)[0]
                 if self.job.kind == "train" else self.token)
        if self.job.collect_metrics:
            # carry the step's metric arrays with the token: they are
            # outputs of the same executable, so by the time the token is
            # ready they are too and float() below costs one host transfer
            return (token, metrics)
        return token

    @staticmethod
    def _token_array(token):
        return token[0] if isinstance(token, tuple) else token

    def _token_ready(self, token) -> bool:
        is_ready = getattr(self._token_array(token), "is_ready", None)
        return is_ready is None or is_ready()

    def _token_wait(self, token) -> None:
        jax.block_until_ready(self._token_array(token))

    def _completion_record(self, dispatch_t: float, token) -> Dict[str, float]:
        rec = super()._completion_record(dispatch_t, token)
        if isinstance(token, tuple):
            rec.update({k: float(v) for k, v in token[1].items()})
        return rec

    # ----------------------------------------------------------- persist
    def _decode_ctx(self) -> Dict[str, Any]:
        """A serve block's generation context — without it a restored
        decoder would silently restart from an empty cache at position 0.
        Paged serve checkpoints the whole continuous-batching plane (page
        pool, page tables, per-slot lengths, session metadata) so in-flight
        generate sessions survive preemption."""
        if self.job.paged:
            return {"paged": self.sessions.state_tree()}
        return {"cache": self.cache, "token": self.token,
                "cache_len": self.cache_len}

    def _abstract_like(self) -> Dict[str, Any]:
        """Restore targets without materializing state on device (resume
        path: a full random init just to overwrite it would put a model-init
        compile on the preemption-resume critical path)."""
        job = self.job
        if job.kind == "train":
            return train_lib.abstract_train_state(job.cfg, job.opt)
        return {"params": model_lib.abstract_params(job.cfg)}

    def _payload(self) -> Dict[str, Any]:
        payload = {"state": self.state, "step_count": self.step_count}
        if self.job.kind == "serve":
            payload["decode"] = self._decode_ctx()
        return payload

    def save(self, async_: bool = True) -> None:
        payload = self._payload()
        if async_:
            self.ckpt.save_async(self.step_count, payload)
        else:
            self.ckpt.save(self.step_count, payload)
        self.last_saved_step = self.step_count

    def release(self) -> None:
        """Expiry: drop every device array the block holds (state, caches,
        the paged pool) so its chips' memory is free before they are
        granted again.  Call after ``drain()``."""
        self.state = None
        self.cache = None
        self.sessions = None
        self.token = None
        if self.app_id is not None:
            PROGRAMS.forget(self.app_id)

    @property
    def progress_lost(self) -> int:
        """Steps of work beyond the last checkpoint — what a *non-graceful*
        eviction of this block would throw away.  The scheduler's victim
        selection minimizes this (suspend() itself checkpoints, so graceful
        preemption loses nothing; the metric bounds the drain/save cost and
        the loss if the host dies mid-suspend)."""
        return max(0, self.step_count - self.last_saved_step)

    def suspend(self) -> Dict[str, float]:
        """Preemption: drain in-flight dispatches, checkpoint synchronously,
        and drop every device reference so the chips can be re-granted.
        The runtime object survives (job spec + checkpoint namespace) and
        can be rebuilt on any chip set with ``resume``."""
        drained = self.drain()
        self.ckpt.wait()                 # an async save may still be landing
        self.save(async_=False)
        self.state = None
        self.cache = None
        if self.job.kind == "serve":
            self.token = None
            self.cache_len = None
            self._prefill_fn = None
            self.sessions = None     # device pool + jits dropped; host
                                     # session state lives in the checkpoint
        self._step = None
        self.mesh = None
        self.devices = []
        self.suspended = True
        return {"step": self.step_count, "drained_steps": len(drained)}

    def resume(self, grant: BlockGrant,
               devices: Sequence[jax.Device]) -> int:
        """Rebuild after preemption on ``devices`` (possibly different chips
        and/or a different mesh geometry than suspend-time) and restore the
        checkpointed state, resharded onto the new mesh.  Returns the step
        the block resumed at."""
        assert self.suspended, "resume() is only legal after suspend()"
        self._attach(grant, devices)
        # no init_state(): restore targets are abstract (shape/dtype), so
        # resume skips the model-init compile entirely
        at = self.restore()
        self.suspended = False
        return at

    def restore(self, step: Optional[int] = None) -> int:
        like = {"state": (self.state if self.state is not None
                          else self._abstract_like()),
                "step_count": self.step_count}
        shardings = {"state": self.state_shardings, "step_count": None}
        if self.job.kind == "serve":
            have_ctx = (self.sessions is not None if self.job.paged
                        else self.cache is not None)
            decode_like = (self._decode_ctx() if have_ctx
                           else self._abstract_decode())
            like["decode"] = decode_like
            # the decode context restores onto the block's own chips, as
            # the init path places it
            shardings["decode"] = jax.tree.map(lambda _: self.replicated,
                                               decode_like)
        restored, at = self.ckpt.restore(like, step=step, shardings=shardings)
        self.state = restored["state"]
        if self.job.kind == "serve":
            dec = restored["decode"]
            if self.job.paged:
                if self.sessions is None:   # resume: rebuild without a
                    self.sessions = self._make_scheduler(   # throwaway pool
                        self.state["params"], init_pool=False)
                self.sessions.params = self.state["params"]
                self.sessions.load_state(dec["paged"])
                self.token = self.sessions.last_tokens_dev
            else:
                self.cache = dec["cache"]
                self.token = dec["token"]
                self.cache_len = dec["cache_len"]
        self.step_count = int(restored["step_count"])
        self.last_saved_step = self.step_count   # state == checkpoint now
        return at

    def _abstract_decode(self) -> Dict[str, Any]:
        # eval_shape: shape/dtype targets only — materializing a real cache
        # here would double peak device memory on the resume critical path
        if self.job.paged:
            from repro.serve.decode_scheduler import DecodeScheduler
            return {"paged": DecodeScheduler.abstract_state(
                self.job.cfg, **self._paged_geometry())}
        shape = self.job.shape
        return jax.eval_shape(lambda: {
            "cache": model_lib.init_cache(self.job.cfg, shape.global_batch,
                                          shape.seq_len),
            "token": jnp.zeros((shape.global_batch, 1), jnp.int32),
            "cache_len": jnp.int32(0),
        })

    @classmethod
    def rebuild(cls, old: "BlockRuntime", grant: BlockGrant,
                devices: Sequence[jax.Device], ckpt_root: str
                ) -> "BlockRuntime":
        """Failure migration / elastic resize: new runtime on new devices,
        state restored from the old block's checkpoints (resharded onto the
        new mesh by the checkpoint manager)."""
        rt = cls(grant, old.job, devices, ckpt_root, app_id=old.app_id)
        rt.init_state()
        old.ckpt.wait()
        if old.ckpt.latest_step() is not None:
            rt.ckpt = old.ckpt      # same namespace: adopt checkpoint history
            rt.restore()
        return rt
