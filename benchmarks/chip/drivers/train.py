"""Training traffic: the program's ``Trainer.run`` over seeded batches.

Set-up builds one ``Trainer`` with weights made from the seed on the device,
and drives it through its first three steps with ``Trainer.run`` itself
(the first compiles). Those steps give the readings the reference checks:
each step's loss, the first gradient (from the optimizer's first moment) and
the weights' change over the three steps. Two more steps time a step, so
that the window's one ``Trainer.run`` call lasts about ``--seconds``.

From set-up on, when the mix has an evaluator, a thread takes read-only
snapshots of (params, opt, data_cursor) through the trainer's store in an
open loop (``StoreWatch``). After the window the store is held to its
guarantees: every step committed once, in order, with the final state; each
snapshot a consistent cut, never older than a commit that came before it.

Traffic keys: ``batch``, ``seq``, ``evaluator_hz`` (0: none),
``timing_steps`` and ``adamw`` (the optimizer's settings).
"""
from __future__ import annotations

import gc
import shutil
import tempfile
import threading
import time
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, program, trainref, weights
from chipbench.harness import Outcome, Session, load_module

SETUP_STEPS = 3
CUT = ("params", "opt", "data_cursor")


def _per_layer_norms(tree, scale: float = 1.0) -> Dict[str, float]:
    """Leaf key -> norm, stacked ``g*`` leaves split per layer."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    paths = [weights._path_str(p) for p, _ in flat]

    @jax.jit
    def fn(leaves):
        return [jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)),
                                 axis=tuple(range(1, a.ndim))))
                if p.startswith("g") else
                jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                for p, a in zip(paths, leaves)]

    out = {}
    for p, n in zip(paths, fn([a for _, a in flat])):
        n = np.asarray(n) * scale
        if p.startswith("g"):
            out.update({f"{p}@{l}": float(x) for l, x in enumerate(n)})
        else:
            out[p] = float(n)
    return out


def _change_norms(params, seed: int, model_type: str) -> Dict[str, float]:
    """Norm of each leaf's change from its seeded start, one leaf at a time
    so the start never sits on the device whole."""
    key = weights.base_key(seed)
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        p = weights._path_str(path)

        @jax.jit
        def fn(a, key, p=p):
            if p.startswith("g"):
                start = jax.vmap(lambda l: weights.leaf(
                    key, model_type, p, l, a.shape[1:]))(jnp.arange(a.shape[0]))
                d = a.astype(jnp.float32) - start
                return jnp.sqrt(jnp.sum(jnp.square(d), axis=tuple(
                    range(1, a.ndim))))
            d = a.astype(jnp.float32) - weights.leaf(key, model_type, p, 0,
                                                     a.shape)
            return jnp.sqrt(jnp.sum(jnp.square(d)))

        n = np.asarray(fn(a, key))
        if p.startswith("g"):
            out.update({f"{p}@{l}": float(x) for l, x in enumerate(n)})
        else:
            out[p] = float(n)
    return out


class _Loss:
    """The step's loss, with the host's sync on it under a span."""

    def __init__(self, s: Session, value):
        self.s, self.value = s, value

    def __float__(self) -> float:
        with self.s.span("loss_sync"):
            return float(self.value)


def build(s: Session):
    """The trainer and its state, from the seed (shared with the control).

    Where the configuration states a ``mesh`` (axis name -> size, e.g.
    ``{"data": 2, "model": 2}``), the program is built for it as the
    program's launcher builds it: its backbone wired for the mesh, its
    parameters, optimizer state and batch laid out by the program's
    sharding rules."""
    from repro.data.pipeline import DataConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.shardings import sharded_backbone, train_state_shardings
    from repro.models import Backbone
    from repro.optim import adamw
    from repro.runtime.steps import StepSettings
    from repro.runtime.train_loop import Trainer, TrainerConfig

    tr, cfg = s.cell.traffic, s.cell.config
    pcfg = program.model_config(cfg)
    settings = StepSettings()
    mesh = params_sh = state_sh = None
    if "mesh" in cfg:
        mesh = make_mesh(tuple(cfg["mesh"].values()), tuple(cfg["mesh"]))
        bb, params_sh = sharded_backbone(pcfg, mesh, tr["batch"], settings)
        state_sh = train_state_shardings(params_sh, mesh, settings)
    else:
        bb = Backbone(pcfg, remat=settings.remat)
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    trainer = Trainer(
        bb, adamw.AdamWConfig(**tr["adamw"]),
        DataConfig(vocab=pcfg.vocab, seq_len=tr["seq"],
                   global_batch=tr["batch"], seed=s.seed),
        TrainerConfig(total_steps=0, ckpt_every=10 ** 12, log_every=10 ** 12,
                      ckpt_dir=ckpt_dir, keep_ckpts=1), settings,
        mesh=mesh, state_shardings=state_sh)
    params = weights.program_tree(bb.param_specs(), s.seed, cfg["model_type"],
                                  jnp.float32, params_sh)
    laid = {} if state_sh is None else {"out_shardings": state_sh}
    state = jax.jit(lambda p: {"params": p, "opt": adamw.init_state(p)},
                    donate_argnums=0, **laid)(params)
    trainer.start_step = 0
    trainer.store.commit_step(None, None, 0)
    return trainer, state, ckpt_dir


def advance(trainer, state, steps: int):
    """``Trainer.run`` over the next ``steps`` steps."""
    trainer.start_step = trainer.tcfg.total_steps
    trainer.tcfg.total_steps += steps
    return trainer.run(state)


def program_readings(s: Session, trainer, state):
    """Steps 1-3 through ``Trainer.run``: the readings the reference checks."""
    b1 = s.cell.traffic["adamw"]["b1"]
    state = advance(trainer, state, 1)
    grad = _per_layer_norms(state["opt"]["m"], 1.0 / (1.0 - b1))
    state = advance(trainer, state, SETUP_STEPS - 1)
    change = _change_norms(state["params"], s.seed,
                           s.cell.config["model_type"])
    losses = [m["loss"] for m in trainer.metrics_log[:SETUP_STEPS]]
    return state, {"loss": losses, "grad": grad, "change": change}


def reference(s: Session, prec: str = "f32") -> Dict:
    """The reference's readings, its rows split over the cell's chips."""
    tr, cfg = s.cell.traffic, s.cell.config
    ref = load_module("refs", cfg["reference"])
    return trainref.run(ref, cfg, s.seed, tr["batch"], tr["seq"],
                        tr["adamw"], prec, SETUP_STEPS,
                        s.devices[:s.cell.chips])


class StoreWatch:
    """The trainer's store, watched from outside: each commit the trainer
    makes as (version, called, returned), and, where the mix has an
    evaluator, a thread's read-only snapshots of (params, opt, data_cursor)
    in an open loop at ``hz`` as (called, returned, their three versions).
    A snapshot never reads array contents: the trainer donates the buffers
    it committed."""
    JOIN_S = 60.0

    def __init__(self, store, hz: float):
        self.store, self.hz = store, hz
        self.commits: List[Tuple[int, float, float]] = []
        self.snaps: List[Tuple[float, float, Tuple[int, ...]]] = []
        self.hung = False
        self._commit = store.commit_step
        store.commit_step = self._commit_step
        self._stop = threading.Event()
        self._thread = None
        if hz:
            self._thread = threading.Thread(target=self._evaluate, daemon=True)
            self._thread.start()

    def _commit_step(self, params, opt, step: int) -> None:
        t = time.perf_counter()
        self._commit(params, opt, step)
        self.commits.append((step, t, time.perf_counter()))

    def _evaluate(self) -> None:
        start, i = time.perf_counter(), 0
        while not self._stop.is_set():
            wait = start + i / self.hz - time.perf_counter()
            if wait > 0 and self._stop.wait(wait):
                break
            t = time.perf_counter()
            snap = self.store.snapshot(CUT, irrevocable=False)
            self.snaps.append((t, time.perf_counter(),
                               tuple(snap[f"{c}_version"] for c in CUT)))
            i += 1

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(self.JOIN_S)
            self.hung = self._thread.is_alive()

    def checks(self, state, steps: int) -> Dict[str, int]:
        """Exact counts, each 0 in a sound run.

        ``store_misses``: steps not committed exactly once and in order,
        cells whose final version is not the number of steps, and params
        or opt whose final value is not the state the trainer returned.
        Where the mix has an evaluator, ``torn_snapshots``: snapshots whose
        three versions differ; ``stale_snapshots``: snapshots that saw a
        version older than one committed before they were called, newer
        than any committed before they returned, or older than the
        snapshot before them."""
        final = self.store.snapshot(CUT, irrevocable=False)
        misses = int([v for v, _, _ in self.commits]
                     != list(range(1, steps + 1)))
        misses += sum(final[f"{c}_version"] != steps for c in CUT)
        misses += sum(final[c] is not state[c] for c in ("params", "opt"))
        out = {"store_misses": misses}
        if not self.hz:
            return out
        torn = stale = 0
        last = 0
        for called, returned, vs in self.snaps:
            v = vs[0]
            torn += len(set(vs)) > 1
            done = max((c for c, _, r in self.commits if r <= called),
                       default=0)
            begun = max((c for c, b, _ in self.commits if b <= returned),
                        default=0)
            stale += not (max(done, last) <= v <= begun)
            last = max(last, v)
        out.update(torn_snapshots=torn, stale_snapshots=stale)
        return out


def run(s: Session) -> Outcome:
    tr, cfg = s.cell.traffic, s.cell.config
    trainer, state, ckpt_dir = build(s)
    watch = StoreWatch(trainer.store, tr["evaluator_hz"])
    try:
        s.note("trainer built")
        state, prog = program_readings(s, trainer, state)
        s.note("steps 1-3 read")
        t = time.perf_counter()
        state = advance(trainer, state, tr["timing_steps"])
        jax.block_until_ready(state)
        per_step = (time.perf_counter() - t) / tr["timing_steps"]
        n = max(2, int(round(s.seconds / per_step)))

        s.wrap(trainer, "_step", "step",
               post=lambda out: (out[0], dict(out[1],
                                              loss=_Loss(s, out[1]["loss"]))))
        s.wrap(trainer.store, "commit_step", "commit")
        with s.window():
            state = advance(trainer, state, n)
            jax.block_until_ready(state)
        watch.stop()
        s.read_memory()
        steps = SETUP_STEPS + tr["timing_steps"] + n
        store_checks = watch.checks(state, steps)
        e2e = {"train_tokens_per_s": n * tr["batch"] * tr["seq"] / s.wall_s}
        print(f"[train] {n} steps in {s.wall_s:.3f}s (set-up estimate "
              f"{per_step * 1e3:.1f} ms/step); {len(watch.snaps)} snapshots; "
              f"losses {prog['loss']}", flush=True)
        layer = {"steps": n, "wall_s": s.wall_s,
                 "step_flops": flops.train_step_flops(cfg, tr["batch"],
                                                      tr["seq"]),
                 "commit_s": s.spans_in_window("commit")}
    finally:
        watch.stop()
        trainer.shutdown()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    hung = watch.hung
    del trainer, state, watch      # the watch holds the store, and it the state
    gc.collect()

    s.note("program freed")
    ref = reference(s)
    s.note("reference done")
    gaps = trainref.compare(prog, ref)
    print(f"[train] readings: {gaps}", flush=True)
    print(f"[train] widest leaves: {trainref.widest(prog, ref)}", flush=True)
    lim = s.cell.limits
    checks = {f"{k}_gap": (gaps[k], lim[k]) for k in lim}
    checks.update({k: (float(v), 0.0) for k, v in store_checks.items()})
    return Outcome(attempted=steps, failed=int(hung), end_to_end=e2e,
                   checks=checks, layer=layer)
