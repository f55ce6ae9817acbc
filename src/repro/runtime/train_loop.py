"""The training runtime: loop, fault tolerance, stragglers, elasticity.

The control plane runs on the transactional store (``repro.txstore``):

* every step commits (params, opt, cursor) as one write transaction —
  readers can never observe a torn step;
* checkpoints are taken by an irrevocable read-only transaction (snapshot
  happens asynchronously per paper §2.7), copied to the host on the
  trainer thread, and written by a background thread
  (``AsyncCheckpointer``); ``run`` waits for the last one to reach disk and
  raises if any save failed;
* crash/restart resumes from the newest atomic checkpoint + the stateless
  data pipeline cursor;
* stragglers are detected by a step-time EWMA z-test; mitigation is a
  pluggable policy (on a real cluster: re-slice the batch / evict the
  slow host — here: recorded + surfaced);
* elastic rescale re-device_puts state under new shardings inside a store
  transaction, so concurrent readers see the old or the new sharding,
  never a mix.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint.store import AsyncCheckpointer, CheckpointStore
from repro.data.pipeline import DataConfig, Pipeline, make_batch
from repro.launch.mesh import dp_axes
from repro.models.backbone import REMAT_POLICIES, Backbone
from repro.obs import metrics as _metrics
from repro.obs import txtrace as _txtrace
from repro.optim import adamw
from repro.runtime import profiling
from repro.runtime.profiling import OFF
from repro.runtime.steps import (StepSettings, compile_train_step,
                                 init_train_state, make_train_step,
                                 memory_limit)
from repro.txstore.store import VersionedStateStore


@dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 25
    log_every: int = 10
    ckpt_dir: str = "/tmp/repro_ckpt"
    straggler_zscore: float = 4.0
    straggler_warmup: int = 10
    keep_ckpts: int = 3


@dataclass
class StragglerStats:
    ewma: float = 0.0
    ewvar: float = 0.0
    n: int = 0
    events: List[Dict[str, float]] = field(default_factory=list)

    def observe(self, dt: float, step: int, z_thresh: float,
                warmup: int) -> bool:
        self.n += 1
        if self.n == 1:
            self.ewma = dt
            return False
        # z against the PRE-update statistics (the outlier must not be
        # allowed to widen the band it is tested against); sd floored at
        # 5% of the mean so warm, uniform phases don't fire on jitter.
        sd = max(np.sqrt(self.ewvar), 0.05 * self.ewma, 1e-9)
        z = (dt - self.ewma) / sd
        hit = self.n > warmup and z > z_thresh
        if hit:
            self.events.append({"step": step, "dt": dt, "z": float(z)})
        else:
            # stragglers are excluded from the running statistics
            alpha = 0.1
            delta = dt - self.ewma
            self.ewma += alpha * delta
            self.ewvar = (1 - alpha) * (self.ewvar + alpha * delta * delta)
        return hit


class Trainer:
    def __init__(self, bb: Backbone, opt_cfg: adamw.AdamWConfig,
                 data_cfg: DataConfig, tcfg: TrainerConfig,
                 settings: StepSettings = StepSettings(),
                 *, mesh=None, state_shardings=None,
                 straggler_hook: Optional[Callable[[Dict], None]] = None):
        self.bb = bb
        self.opt_cfg = opt_cfg
        self.data_cfg = data_cfg
        self.tcfg = tcfg
        self.settings = settings
        self.mesh = mesh
        self.state_shardings = state_shardings
        self.straggler_hook = straggler_hook

        self.store = VersionedStateStore()
        profiling.watch_compiles()
        self.ckpt = CheckpointStore(tcfg.ckpt_dir)
        self.async_ckpt = AsyncCheckpointer(
            self.ckpt, on_done=self._on_ckpt_done)
        self.straggler = StragglerStats()
        self.metrics_log: List[Dict[str, float]] = []

        if mesh is not None and state_shardings is not None:
            # the batch splits over the data axes; state keeps its layout
            # from step to step, so each output can be donated back in
            batch_sh = NamedSharding(mesh, P(dp_axes(mesh) or None))
            self._jit_kw = dict(
                in_shardings=(state_shardings, batch_sh),
                out_shardings=(state_shardings, NamedSharding(mesh, P())),
                donate_argnums=(0,))
        else:
            self._jit_kw = dict(donate_argnums=(0,))
        self._compiled = None
        #: the checkpoint policy the train step compiled with (None: its
        #: layers are not checkpointed, or it has not compiled yet)
        self.remat_policy: Optional[str] = None

    def _step(self, state: Dict[str, Any], batch: Dict[str, Any]):
        """One train step; the first call compiles it."""
        if self._compiled is None:
            self._compiled = self._compile_step(state, batch)
        return self._compiled(state, batch)

    def _compile_step(self, state, batch):
        """The train step compiled under the first checkpoint policy whose
        program fits the devices the step runs on (``compile_train_step``;
        the one ``settings`` names, where it names one), recorded on the
        ``trainer`` site's metrics: a count of compiles per policy, and the
        compiled step's temporary bytes, the saved residuals among them."""
        def build(policy):
            settings = dataclasses.replace(self.settings, remat_policy=policy)
            return jax.jit(make_train_step(self.bb, self.opt_cfg, settings),
                           **self._jit_kw)

        policies = ((None,) if not self.bb.remat
                    else (self.settings.remat_policy,)
                    if self.settings.remat_policy else tuple(REMAT_POLICIES))
        compiled, self.remat_policy = compile_train_step(
            build, policies, (state, batch), memory_limit((state, batch)))
        mem = compiled.memory_analysis()
        reg = _metrics.registry(profiling.TRAINER.site)
        reg.counter(f"train_step.remat.{self.remat_policy or 'none'}").inc()
        reg.counter("train_step.temp_bytes").inc(mem.temp_size_in_bytes)
        print(f"[trainer] train step compiled with remat policy "
              f"{self.remat_policy}: temp {mem.temp_size_in_bytes} bytes, "
              f"peak {mem.peak_memory_in_bytes} bytes", flush=True)
        return compiled

    # ------------------------------------------------------------------ #
    def _on_ckpt_done(self, step: int, path: str) -> None:
        self.store.record_checkpoint(step, path)
        self.ckpt.gc(self.tcfg.keep_ckpts)

    def init_or_restore(self, seed: int = 0) -> Dict[str, Any]:
        """Fresh init, or resume from the newest checkpoint (crash restart)."""
        latest = self.ckpt.latest_step()
        template = jax.eval_shape(
            lambda k: init_train_state(self.bb, k, self.settings),
            jax.random.PRNGKey(seed))
        if latest is not None:
            zeros = jax.tree_util.tree_map(
                lambda s: np.zeros(s.shape, s.dtype), template)
            state, step = self.ckpt.restore(zeros, latest,
                                            shardings=self.state_shardings)
            self.start_step = step
            print(f"[trainer] resumed from checkpoint step {step}")
        else:
            # jitted so a sharded state is born sharded: it may not fit on
            # one device whole
            init = lambda k: init_train_state(self.bb, k, self.settings)
            init = (jax.jit(init) if self.state_shardings is None else
                    jax.jit(init, out_shardings=self.state_shardings))
            state = init(jax.random.PRNGKey(seed))
            self.start_step = 0
        self.store.commit_step(None, None, self.start_step)  # cursor only
        return state

    # ------------------------------------------------------------------ #
    def run(self, state: Dict[str, Any], *, crash_at: Optional[int] = None
            ) -> Dict[str, Any]:
        """Train from ``start_step`` to ``tcfg.total_steps``.

        While ``txtrace.enabled`` is on (``run`` turns it on for its own
        length when it starts under a JAX profile, and then writes the
        profiling anchor first), each step is one
        ``StepTraceAnnotation("train", step_num=step)`` holding one span
        per phase: ``train.batch`` (``next(pipe)``), ``train.dispatch``
        (the step call: host-to-device copy and enqueue),
        ``train.loss_sync`` (``float(loss)``), ``train.commit``
        (``store.commit_step``) and, on save steps, ``train.ckpt``. Each is
        a profiler annotation and a txtrace span on the ``trainer`` site
        (``repro.runtime.profiling``), on the site's monotonic clock, on
        this thread only. The store's transaction events (``txn``,
        ``commit``, ``vwait``, ``lw_apply``, ...) land on the same site,
        inside ``train.commit``, and lengthen it by their writing. With
        tracing off each site costs one attribute read."""
        pipe = Pipeline(self.data_cfg, start_step=self.start_step)
        # the store's cells stamp their own events with the site; the
        # transaction's client-side spans (``txn``, ``commit``) go to this
        # thread's tracer, so it is bound to the site for run's length
        bound = _txtrace.thread_tracer()
        _txtrace.set_thread_tracer(profiling.TRAINER)
        profiled = profiling.profiled()
        switched = profiled and not _txtrace.enabled
        if switched:
            _txtrace.enable()
        if profiled:
            profiling.anchor()
        try:
            for step in range(self.start_step, self.tcfg.total_steps):
                on = _txtrace.enabled
                with profiling.step_span(step) if on else OFF:
                    state = self._step_once(state, step, pipe, on, crash_at)
        finally:
            if switched:
                _txtrace.disable()
            _txtrace.set_thread_tracer(bound)
        self.async_ckpt.drain()
        return state

    def _step_once(self, state: Dict[str, Any], step: int, pipe: Pipeline,
                   on: bool, crash_at: Optional[int]) -> Dict[str, Any]:
        """One step of ``run``; ``on``: tracing was on when it began."""
        phase = profiling.phase
        with phase("train.batch", step) if on else OFF:
            batch = next(pipe)
        t0 = time.monotonic()
        if crash_at is not None and step == crash_at:
            raise RuntimeError(f"injected crash at step {step}")
        with phase("train.dispatch", step) if on else OFF:
            state, metrics = self._step(state, batch)
        with phase("train.loss_sync", step) if on else OFF:
            loss = float(metrics["loss"])
        dt = time.monotonic() - t0
        if self.straggler.observe(step=step, dt=dt,
                                  z_thresh=self.tcfg.straggler_zscore,
                                  warmup=self.tcfg.straggler_warmup):
            ev = self.straggler.events[-1]
            print(f"[straggler] step {step}: {dt*1e3:.1f}ms "
                  f"(z={ev['z']:.1f}) — mitigation hook invoked")
            if self.straggler_hook:
                self.straggler_hook(ev)
        self.metrics_log.append({"step": step, "loss": loss, "dt": dt})
        # control-plane commit: one write txn over (params, opt, cursor)
        with phase("train.commit", step) if on else OFF:
            self.store.commit_step(state["params"], state["opt"], step + 1)
        if (step + 1) % self.tcfg.ckpt_every == 0:
            # irrevocable read-only txn -> consistent async snapshot;
            # materialize to host NOW (the copy-buffer copy): the live
            # device buffers are donated into the next step
            with phase("train.ckpt", step) if on else OFF:
                snap = self.store.snapshot(("params", "opt", "data_cursor"))
                host = jax.device_get({"params": snap["params"],
                                       "opt": snap["opt"]})
                self.async_ckpt.submit(host, snap["data_cursor"])
        if (step + 1) % self.tcfg.log_every == 0:
            print(f"[train] step {step+1}: loss={loss:.4f} "
                  f"({dt*1e3:.0f}ms/step)")
        return state

    def shutdown(self) -> None:
        self.async_ckpt.stop()
        self.store.shutdown()


# --------------------------------------------------------------------------- #
# Elastic rescale                                                              #
# --------------------------------------------------------------------------- #
def rescale_state(state: Any, new_shardings: Any) -> Any:
    """Re-place every leaf under the new mesh's shardings (elastic event).

    On a real cluster this runs after re-forming the mesh with the surviving
    hosts; the transactional store serializes it against readers so nobody
    observes a half-resharded tree.
    """
    return jax.tree_util.tree_map(
        lambda a, s: jax.device_put(a, s), state, new_shardings)
