"""Runtime tests: data pipeline, checkpointing, txstore, trainer FT."""
import shutil
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.store import (AsyncCheckpointer, CheckpointError,
                                    CheckpointStore)
from repro.data.pipeline import DataConfig, Pipeline, make_batch
from repro.models import Backbone, LayerGroup, ModelConfig
from repro.optim import adamw
from repro.runtime.steps import (StepSettings, init_train_state,
                                 make_train_step)
from repro.txstore.store import VersionedStateStore

SMALL = ModelConfig(name="rt-test", family="dense", d_model=64, n_heads=4,
                    n_kv_heads=2, d_ff=128, vocab=256,
                    groups=(LayerGroup(("attn",), 2),))
SETTINGS = StepSettings(zero3=False, gather_weights=False, remat=False)


# --------------------------------------------------------------------------- #
# Data pipeline                                                                #
# --------------------------------------------------------------------------- #
def test_pipeline_deterministic_and_restorable():
    cfg = DataConfig(vocab=256, seq_len=16, global_batch=4)
    a = [next(Pipeline(cfg, i)) for i in range(3)]
    b = list(zip(range(3), Pipeline(cfg, 0)))
    for (i, bb), aa in zip(b, a):
        np.testing.assert_array_equal(aa["tokens"], bb["tokens"])
    # restore mid-stream
    p = Pipeline(cfg, 0)
    next(p); next(p)
    p.restore(1)
    np.testing.assert_array_equal(next(p)["tokens"], a[1]["tokens"])


def test_pipeline_labels_are_shifted_tokens():
    cfg = DataConfig(vocab=128, seq_len=8, global_batch=2)
    batch = make_batch(cfg, 0)
    assert batch["tokens"].shape == (2, 8)
    assert batch["labels"].shape == (2, 8)
    assert batch["tokens"].max() < 128


# --------------------------------------------------------------------------- #
# Checkpoint store                                                             #
# --------------------------------------------------------------------------- #
def test_checkpoint_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path))
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": {"c": jnp.ones((4,))}}
    store.save(tree, 7)
    assert store.latest_step() == 7
    zeros = jax.tree_util.tree_map(lambda a: np.zeros(a.shape, a.dtype), tree)
    got, step = store.restore(zeros)
    assert step == 7
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), got, tree)


def test_checkpoint_gc_and_latest(tmp_path):
    store = CheckpointStore(str(tmp_path))
    tree = {"a": jnp.zeros((2,))}
    for s in (1, 2, 3, 4, 5):
        store.save(tree, s)
    store.gc(keep=2)
    assert store.latest_step() == 5
    got, step = store.restore(
        {"a": np.zeros((2,), np.float32)})
    assert step == 5


def test_async_checkpointer_writes_and_reports(tmp_path):
    store = CheckpointStore(str(tmp_path))
    done = []
    ac = AsyncCheckpointer(store, on_done=lambda s, p: done.append(s))
    ac.submit({"a": jnp.ones((3,))}, 10)
    ac.stop()
    assert ac.saved == [10] and done == [10] and ac.errors == []
    assert store.latest_step() == 10


def test_async_checkpointer_raises_on_failed_save(tmp_path):
    """A save that fails must surface from drain/stop, not end the run
    looking healthy."""
    store = CheckpointStore(str(tmp_path))

    def broken_save(tree, step):
        raise OSError("disk full")

    store.save = broken_save
    ac = AsyncCheckpointer(store)
    ac.submit({"a": jnp.ones((3,))}, 10)
    with pytest.raises(CheckpointError, match="disk full"):
        ac.drain()
    with pytest.raises(CheckpointError):
        ac.stop()
    assert not ac._thread.is_alive()
    assert store.latest_step() is None


# --------------------------------------------------------------------------- #
# Transactional state store                                                    #
# --------------------------------------------------------------------------- #
def test_txstore_snapshot_is_consistent_cut():
    """A snapshot must never observe params from step N with cursor N+1."""
    store = VersionedStateStore()
    bad = []
    stop = threading.Event()

    def trainer():
        step = 0
        while not stop.is_set():
            step += 1
            store.commit_step({"w": step}, {"m": step}, step)

    def checker():
        for _ in range(30):
            snap = store.snapshot(("params", "opt", "data_cursor"))
            if snap["params"] is None:
                continue
            if not (snap["params"]["w"] == snap["opt"]["m"]
                    == snap["data_cursor"]):
                bad.append(snap)

    t = threading.Thread(target=trainer)
    c = threading.Thread(target=checker)
    t.start(); c.start(); c.join(); stop.set(); t.join()
    store.shutdown()
    assert bad == []


def test_txstore_checkpoint_metadata_roundtrip():
    store = VersionedStateStore()
    store.record_checkpoint(5, "/tmp/x/step_5")
    meta = store.latest_checkpoint()
    store.shutdown()
    assert meta["step"] == 5 and meta["path"].endswith("step_5")


# --------------------------------------------------------------------------- #
# Trainer: loss goes down; crash/restart resumes equivalently                  #
# --------------------------------------------------------------------------- #
def _mk_trainer(tmpdir, total=24, ckpt_every=8):
    from repro.runtime.train_loop import Trainer, TrainerConfig
    bb = Backbone(SMALL, compute_dtype=jnp.float32, remat=False)
    return Trainer(
        bb,
        adamw.AdamWConfig(lr=2e-3, warmup_steps=4, total_steps=total),
        DataConfig(vocab=SMALL.vocab, seq_len=16, global_batch=4),
        __import__("repro.runtime.train_loop", fromlist=["TrainerConfig"]
                   ).TrainerConfig(total_steps=total, ckpt_every=ckpt_every,
                                   ckpt_dir=str(tmpdir), log_every=1000),
        SETTINGS)


def test_trainer_loss_decreases(tmp_path):
    tr = _mk_trainer(tmp_path)
    try:
        state = tr.init_or_restore()
        tr.run(state)
        losses = [m["loss"] for m in tr.metrics_log]
        assert losses[-1] < losses[0]
        assert tr.async_ckpt.errors == []
        assert tr.ckpt.latest_step() is not None
    finally:
        tr.shutdown()


def test_trainer_crash_restart_matches_uninterrupted(tmp_path):
    # uninterrupted run
    d1 = tmp_path / "a"
    tr = _mk_trainer(d1)
    try:
        tr.run(tr.init_or_restore())
        ref_losses = {m["step"]: m["loss"] for m in tr.metrics_log}
    finally:
        tr.shutdown()
    # crashed + resumed run
    d2 = tmp_path / "b"
    tr1 = _mk_trainer(d2)
    try:
        with pytest.raises(RuntimeError):
            tr1.run(tr1.init_or_restore(), crash_at=13)
    finally:
        tr1.shutdown()
    tr2 = _mk_trainer(d2)
    try:
        state = tr2.init_or_restore()
        assert tr2.start_step == 8          # resumed from the checkpoint
        tr2.run(state)
        res_losses = {m["step"]: m["loss"] for m in tr2.metrics_log}
    finally:
        tr2.shutdown()
    # post-resume losses match the uninterrupted run exactly (determinism)
    for step in range(8, 24):
        np.testing.assert_allclose(res_losses[step], ref_losses[step],
                                   rtol=1e-5)


def test_straggler_detection():
    from repro.runtime.train_loop import StragglerStats
    st = StragglerStats()
    hits = []
    for step in range(40):
        dt = 0.1 if step != 30 else 2.0
        if st.observe(dt, step, z_thresh=4.0, warmup=10):
            hits.append(step)
    assert hits == [30]


def test_grad_compression_error_feedback():
    grads = {"w": jnp.array([0.301, -0.5, 0.0009])}
    err = {"w": jnp.zeros((3,))}
    total = jnp.zeros((3,))
    for _ in range(50):
        deq, err = adamw.compress_with_feedback(grads, err)
        total = total + deq["w"]
    # error feedback: mean dequantized gradient converges to the true one
    np.testing.assert_allclose(np.asarray(total / 50),
                               np.asarray(grads["w"]), atol=2e-3)


def test_elastic_rescale_state_and_store():
    """Elastic event: re-place state under new shardings inside a store txn;
    readers see old or new, never a mix."""
    from repro.runtime.train_loop import rescale_state

    store = VersionedStateStore()
    try:
        dev = jax.devices()[0]
        sh = jax.sharding.SingleDeviceSharding(dev)
        state = {"w": jnp.arange(8.0), "m": jnp.ones((4,))}
        store.commit_step(state, {"v": jnp.zeros((2,))}, 1)
        new_sh = jax.tree_util.tree_map(lambda _: sh, state)
        store.rescale(lambda tree: rescale_state(tree, new_sh)
                      if tree is not None and not isinstance(tree, dict)
                      or isinstance(tree, dict) and "w" in tree else tree)
        snap = store.snapshot(("params",))
        np.testing.assert_array_equal(np.asarray(snap["params"]["w"]),
                                      np.arange(8.0))
        assert snap["params"]["w"].sharding == sh
    finally:
        store.shutdown()


def test_trainer_straggler_hook_invoked(tmp_path):
    events = []
    from repro.runtime.train_loop import Trainer, TrainerConfig
    bb = Backbone(SMALL, compute_dtype=jnp.float32, remat=False)
    tr = Trainer(bb, adamw.AdamWConfig(lr=1e-3, total_steps=5),
                 DataConfig(vocab=SMALL.vocab, seq_len=16, global_batch=4),
                 TrainerConfig(total_steps=5, ckpt_every=100,
                               ckpt_dir=str(tmp_path), log_every=1000),
                 SETTINGS, straggler_hook=events.append)
    try:
        # force the detector: tiny warmup + injected slow observation
        tr.straggler.n = 20
        tr.straggler.ewma = 0.001
        tr.straggler.ewvar = 1e-10
        state = tr.init_or_restore()
        tr.run(state)
        # first real step (~ms) vs ewma 1us -> fires
        assert len(events) >= 1
    finally:
        tr.shutdown()


def test_microbatching_matches_full_batch():
    """k-way gradient accumulation must produce the same update as the
    full-batch step (mean CE is linear in microbatch means here)."""
    bb = Backbone(SMALL, compute_dtype=jnp.float32, remat=False)
    s1 = StepSettings(zero3=False, gather_weights=False, remat=False,
                      microbatches=1)
    s4 = StepSettings(zero3=False, gather_weights=False, remat=False,
                      microbatches=4)
    state = init_train_state(bb, jax.random.PRNGKey(0), s1)
    batch = make_batch(DataConfig(vocab=SMALL.vocab, seq_len=16,
                                  global_batch=8), 0)
    step1 = jax.jit(make_train_step(bb, adamw.AdamWConfig(lr=1e-3), s1))
    step4 = jax.jit(make_train_step(bb, adamw.AdamWConfig(lr=1e-3), s4))
    out1, m1 = step1(state, batch)
    state2 = init_train_state(bb, jax.random.PRNGKey(0), s4)
    out4, m4 = step4(state2, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(out1["params"]),
                    jax.tree_util.tree_leaves(out4["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-4)
