"""Whole runs of tiny cells on the CPU, with the timed path broken underneath:
``correct`` must come out false for every fault a cell can have, and the
control (the reference in float8) must read worse than the program.

Each run skips only the harness's look for a chip; set-up, the window and
the comparison with the reference are the benchmark's own. The limits are
the cells' own limit files.
"""
import json
import math
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import tinycell  # noqa: E402
from chipbench import trainref  # noqa: E402
from chipbench.harness import load_module  # noqa: E402

LIMITS = os.path.join(os.path.dirname(HERE), "limits")


def limits(workload: str) -> dict:
    with open(os.path.join(LIMITS, f"{workload}.json")) as f:
        return json.load(f)


def correct(outcome) -> bool:
    return outcome.failed == 0 and all(
        math.isfinite(v) and v <= lim for v, lim in outcome.checks.values())


def run(kind: str, workload, seconds: float = 0.3):
    """A tiny cell under ``workload``'s limit file; a serving cell, which no
    workload holds yet, under a served token's logit gap of 0.5."""
    c = tinycell.cell(kind, limits(workload) if workload else
                      {"logit_gap": 0.5})
    drv = load_module("drivers", c.traffic["kind"])
    return drv.run(tinycell.session(c, seed=17, seconds=seconds))


def _wrap_step(monkeypatch, change):
    from repro.runtime import train_loop
    real = train_loop.make_train_step

    def make(*a, **k):
        step = real(*a, **k)
        return lambda state, batch: change(step, state, batch)

    monkeypatch.setattr(train_loop, "make_train_step", make)


def _unchanged(step, state, batch):
    return state, step(state, batch)[1]


def _half_batch(step, state, batch):
    n = batch["tokens"].shape[0] // 2
    return step(state, {k: v[:n] for k, v in batch.items()})


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state-unchanged", "half-batch"])
def test_training_faults_are_not_correct(monkeypatch, fault):
    _wrap_step(monkeypatch, fault)
    out = run("train", "qwen3-4b.train.eval")
    assert not correct(out), out.checks


def _no_commit(real):
    return lambda self, params, opt, step: None


def _third_commit_skipped(real):
    def commit(self, params, opt, step):
        if step != 3:
            real(self, params, opt, step)
    return commit


def _torn(real):
    def snapshot(self, cells=("params", "opt", "data_cursor"), **k):
        out = real(self, cells, **k)
        if "opt_version" in out:
            out["opt_version"] -= 1
        return out
    return snapshot


def _one_behind(real):
    held = {}

    def snapshot(self, cells=("params", "opt", "data_cursor"), **k):
        out = real(self, cells, **k)
        prev = held.get(id(self), out)
        held[id(self)] = out
        return prev
    return snapshot


@pytest.mark.parametrize("method,fault,check", [
    ("commit_step", _no_commit, "store_misses"),
    ("commit_step", _third_commit_skipped, "stale_snapshots"),
    ("snapshot", _torn, "torn_snapshots"),
    ("snapshot", _one_behind, "stale_snapshots")],
    ids=["commit-skipped", "third-commit-skipped", "snapshot-torn",
         "snapshot-stale"])
def test_store_faults_are_not_correct(monkeypatch, method, fault, check):
    """The trainer's store broken underneath: a commit that does nothing,
    one step's commit skipped, a snapshot whose versions differ, a snapshot
    that answers with the one before it."""
    from repro.txstore.store import VersionedStateStore
    real = getattr(VersionedStateStore, method)
    monkeypatch.setattr(VersionedStateStore, method, fault(real))
    out = run("train", "qwen3-4b.train.eval")
    assert out.checks[check][0] > 0, out.checks
    assert not correct(out), out.checks


def test_serving_token_altered_is_not_correct(monkeypatch):
    import jax.numpy as jnp
    from repro.models.backbone import Backbone
    real = Backbone.decode_step

    def altered(self, params, cache, tokens):
        logits, cache = real(self, params, cache, tokens)
        return jnp.roll(logits, self.cfg.vocab // 2, axis=-1), cache

    monkeypatch.setattr(Backbone, "decode_step", altered)
    out = run("serve", None)
    assert not correct(out), out.checks


def test_unbroken_tiny_runs_measure_and_compare():
    out = run("train", "qwen3-4b.train.eval")
    assert out.end_to_end["train_tokens_per_s"] > 0
    assert set(out.checks) == {"loss_gap", "grad_gap", "change_gap",
                               "store_misses", "torn_snapshots",
                               "stale_snapshots"}
    assert out.checks["change_gap"][0] < 0.5
    assert correct(out), out.checks
    out = run("serve", None)
    assert out.failed == 0 and out.attempted > 0
    assert out.end_to_end["ttft_p95_ms"] > 0


@pytest.mark.parametrize("kind", ["train", "rwkv"])
def test_training_control_reads_worse_than_the_program(kind):
    """The reference with float8 matmul operands, in the program's place,
    departs from the float32 reference further than the program does."""
    c = tinycell.cell(kind, {})
    drv = load_module("drivers", "train")
    s = tinycell.session(c, seed=23)
    trainer, state, ckpt = drv.build(s)
    try:
        _, prog = drv.program_readings(s, trainer, state)
    finally:
        trainer.shutdown()
    ref = drv.reference(s)
    program = trainref.compare(prog, ref)
    control = trainref.compare(drv.reference(s, "fp8"), ref)
    assert max(control[k] / max(program[k], 1e-12) for k in control) > 3


def test_serving_control_reads_worse_than_the_program():
    import numpy as np
    import jax.numpy as jnp
    from chipbench import weights
    c = tinycell.cell("serve", {})
    ref = load_module("refs", "qwen3")
    rng = np.random.default_rng(0)
    seqs = [jnp.asarray(rng.integers(0, 512, 40, dtype=np.int32))
            for _ in range(3)]
    rows = [(8, 32)] * 3
    get = weights.leaf_fn(29, "qwen3", jnp.bfloat16)
    f32 = ref.served_logits(get, c.config, seqs, rows, "f32", 48)
    f8 = ref.served_logits(get, c.config, seqs, rows, "fp8", 48)
    gap = max(float((np.asarray(a).max(-1) - np.take_along_axis(
        np.asarray(a), np.asarray(b).argmax(-1)[:, None], -1)[:, 0]).max())
        for a, b in zip(f32, f8))
    assert gap > 0


def test_the_program_is_freed_before_the_reference_runs(monkeypatch):
    """On the chip the reference needs the memory the program held: when it
    starts, no array as large as the embedding may be left alive."""
    import jax
    c = tinycell.cell("train", limits("qwen3-4b.train.eval"))
    drv = load_module("drivers", "train")
    real, live = drv.reference, []

    def reference(s, prec="f32"):
        live.append(sum(a.size for a in jax.live_arrays()))
        return real(s, prec)

    monkeypatch.setattr(drv, "reference", reference)
    drv.run(tinycell.session(c, seed=17, seconds=0.3))
    assert live and live[0] < c.config["vocab_size"] * c.config["hidden_size"]
