"""Trainer.run's spans: with tracing on, one step span per step holding one
span per phase, with the step as identifier, and the store's transaction
events of each commit inside that step's commit span; with tracing off,
nothing recorded and no ring made; under a JAX profile, tracing on for the
run's length, after an anchor."""
from collections import Counter

import jax.numpy as jnp
import pytest

from repro.data.pipeline import DataConfig
from repro.models import Backbone, LayerGroup, ModelConfig
from repro.obs import compiles, txtrace
from repro.optim import adamw
from repro.runtime import profiling
from repro.runtime.steps import StepSettings
from repro.runtime.train_loop import Trainer, TrainerConfig

SMALL = ModelConfig(name="spans-test", family="dense", d_model=32, n_heads=2,
                    n_kv_heads=1, d_ff=64, vocab=128,
                    groups=(LayerGroup(("attn",), 1),))
SETTINGS = StepSettings(zero3=False, gather_weights=False, remat=False)
PHASES = ("train.batch", "train.dispatch", "train.loss_sync", "train.commit")
STEPS = 4


@pytest.fixture
def trainer(tmp_path):
    tr = Trainer(Backbone(SMALL, compute_dtype=jnp.float32, remat=False),
                 adamw.AdamWConfig(lr=1e-3, total_steps=STEPS),
                 DataConfig(vocab=SMALL.vocab, seq_len=8, global_batch=2),
                 TrainerConfig(total_steps=STEPS, ckpt_every=2,
                               ckpt_dir=str(tmp_path), log_every=1000),
                 SETTINGS)
    state = tr.init_or_restore()
    was = txtrace.enabled
    profiling.drain()
    compiles.LOG.reset()
    try:
        yield tr, state
    finally:
        txtrace.enabled = was
        profiling.drain()
        compiles.LOG.reset()
        tr.shutdown()


def test_a_traced_run_has_one_span_per_phase_per_step(trainer):
    tr, state = trainer
    txtrace.enable()
    tr.run(state)
    txtrace.disable()
    evs = profiling.drain()
    steps = {e["pv"]: e for e in evs if e["kind"] == profiling.STEP}
    assert sorted(steps) == list(range(STEPS))
    ring = steps[0]["ring"]
    spans = [e for e in evs if e["kind"].startswith("train.")]
    assert all(e["ring"] == ring for e in spans)
    count = Counter((e["kind"], e["pv"]) for e in spans)
    for s in range(STEPS):
        assert all(count[(k, s)] == 1 for k in PHASES)
        # saves on the steps that end a ckpt_every group
        assert count[("train.ckpt", s)] == (s % 2 == 1)
    for e in spans:
        st = steps[e["pv"]]
        assert st["ts"] <= e["ts"]
        assert e["ts"] + e["dur"] <= st["ts"] + st["dur"]
    # phases in order, none overlapping
    for s in range(STEPS):
        mine = sorted((e for e in spans if e["pv"] == s),
                      key=lambda e: e["ts"])
        assert [e["kind"] for e in mine][:4] == list(PHASES)
        assert all(a["ts"] + a["dur"] <= b["ts"]
                   for a, b in zip(mine, mine[1:]))


def test_each_commits_transaction_events_lie_inside_its_commit_span(trainer):
    tr, state = trainer
    txtrace.enable()
    tr.run(state)
    txtrace.disable()
    evs = profiling.drain()
    commits = {e["pv"]: e for e in evs if e["kind"] == "train.commit"}
    ring = commits[0]["ring"]
    txns = [e for e in evs if e["kind"] == "txn" and e["ring"] == ring]
    # one write transaction per step, and (on save steps) one snapshot
    assert len(txns) == STEPS + STEPS // 2
    for t in txns:
        mine = [e for e in evs if e["txn"] == t["txn"]]
        assert {"commit", "dispense"} <= {e["kind"] for e in mine}
        host = [c for c in commits.values()
                if c["ts"] <= t["ts"] <= c["ts"] + c["dur"]]
        if not host:            # a save step's snapshot, under train.ckpt
            continue
        (c,) = host
        for e in mine:
            assert c["ts"] <= e["ts"]
            assert e["ts"] + e["dur"] <= c["ts"] + c["dur"]
    assert sum(1 for t in txns if any(
        c["ts"] <= t["ts"] <= c["ts"] + c["dur"]
        for c in commits.values())) == STEPS


def test_an_untraced_run_records_nothing_and_makes_no_ring(trainer):
    tr, state = trainer
    txtrace.disable()
    tr.run(state)
    assert profiling.TRAINER.events() == []
    assert profiling.TRAINER._rings == []


def test_a_run_under_a_profile_traces_itself_and_anchors(trainer, tmp_path):
    import jax

    tr, state = trainer
    txtrace.disable()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert profiling.profiled()
        tr.run(state)
    finally:
        jax.profiler.stop_trace()
    assert not txtrace.enabled
    evs = profiling.drain()
    (anchor,) = [e for e in evs if e["kind"] == profiling.ANCHOR]
    steps = [e for e in evs if e["kind"] == profiling.STEP]
    assert len(steps) == STEPS and anchor["ts"] < steps[0]["ts"]


def test_the_train_steps_compile_is_logged_at_its_step(trainer):
    tr, state = trainer
    txtrace.enable()
    tr.run(state)
    txtrace.disable()
    built = [r for r in compiles.LOG.records if "train_step" in r[0]]
    assert [(r[1], r[3]) for r in built] == [
        ("trace", 0), ("lower", 0), ("compile", 0)]
    assert all(r[2] > 0 for r in built)


def test_a_commit_blocked_behind_a_reader_records_the_wait(trainer):
    """A read-only transaction that is still open when the trainer commits
    holds the commit condition: the trainer's wait is a ``vwait`` span on
    its own ring, inside that step's ``train.commit``."""
    import threading
    import time

    from repro.core import Transaction

    tr, state = trainer
    tr.tcfg.total_steps = 1
    state = tr.run(state)               # compiled before the reader opens
    tr.start_step, tr.tcfg.total_steps = 1, 2
    store, opened = tr.store, threading.Event()

    def read():
        t = Transaction(store.registry, irrevocable=False)
        cells = [t.reads(store.cells[c], 1) for c in ("params", "opt")]

        def body(_t):
            for c in cells:
                c.get()
            opened.set()
            time.sleep(0.2)

        t.start(body)

    reader = threading.Thread(target=read)
    txtrace.enable()
    reader.start()
    assert opened.wait(10)
    tr.run(state)
    reader.join()
    txtrace.disable()
    evs = profiling.drain()
    (commit,) = [e for e in evs if e["kind"] == "train.commit"]
    waits = [e for e in evs if e["kind"] == "vwait"
             and e["ring"] == commit["ring"]]
    assert waits and all(
        commit["ts"] <= w["ts"] and
        w["ts"] + w["dur"] <= commit["ts"] + commit["dur"] for w in waits)
    assert sum(w["dur"] for w in waits) > 0.05
