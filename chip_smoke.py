#!/usr/bin/env python3
"""Chip smoke: the trainer, the server and the Pallas kernels on one TPU.

    python3 chip_smoke.py [--seed N]    # one chip: device, train, serve, kernels
    python3 chip_smoke.py --chips 4     # four chips: the sharded trainer only

One process drives every phase; it never starts a JAX child. Phases:

1. device  -- ``jax.devices()[0]`` must be a TPU. There is no CPU fallback.
2. train   -- ``Trainer`` (each step one OptSVA-CF write transaction through
   ``txstore``) at qwen3-4b's full widths, cut to ``TRAIN_LAYERS`` layers:
   bf16 compute over fp32 master parameters, a checkpoint at the last step
   taken as an irrevocable read-only snapshot and written to disk.
3. serve   -- ``Server`` over all 36 layers of qwen3-4b with bf16
   parameters; request 0's greedy tokens are checked against one forward
   pass over its prompt plus its generated tokens.
4. kernels -- the compiled RWKV-6, RG-LRU and flash-attention kernels at
   real widths against their ``kernels/ref.py`` oracles, forward and grad.

``--chips 4`` runs the trainer sharded over a 2x2 ``(data, model)`` mesh at
a depth whose state no single chip holds, and checks an L=2 first-step
loss on that mesh against the same step on one device.

Weights and data are made from ``--seed``. Times and memory printed here are
smoke diagnostics, not benchmark metrics. The last line of a run whose every
check passed is ``{"ok": true, "device": {...}}``; a failed check exits
non-zero without it.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.data.pipeline import DataConfig  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.shardings import (sharded_backbone,  # noqa: E402
                                    train_state_shardings)
from repro.models import Backbone, get_config  # noqa: E402
from repro.models.config import LayerGroup  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.runtime.serve_loop import Request, Server  # noqa: E402
from repro.runtime.steps import StepSettings  # noqa: E402
from repro.runtime.train_loop import Trainer, TrainerConfig  # noqa: E402

ARCH = "qwen3-4b"
GiB = 2 ** 30
# Deepest cut whose train step fits one 16 GiB v5e: 14.52 GiB compiled peak
# at batch 4 x seq 512 with the layers' projections saved for the backward,
# 13.97 with full remat (tests/test_tpu_compile.py keeps it under 16 GiB).
TRAIN_LAYERS = 5
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 512, 8
SERVE_SLOTS, SERVE_REQUESTS, PROMPT_LEN, MAX_NEW = 4, 8, 64, 16
# four-chip phase: 1.2B parameters, ~19 GB of state (fp32 params, m, v)
MESH_LAYERS, MESH_STEPS, COMPARE_LAYERS = 8, 3, 2
# Two bf16 paths (cached decode vs one full forward) may order a near-tie
# differently: a served token passes if its reference logit is within this
# of the reference maximum.
TIE_TOL = 0.125
SMOKE_DIR = ROOT / ".smoke"


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
    if not ok:
        raise SmokeFailure(what)


def cut(layers: int):
    cfg = get_config(ARCH)
    return cfg, dataclasses.replace(cfg, groups=(LayerGroup(("attn",), layers),))


def tree_bytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(tree))


def make_trainer(bb, cfg, steps: int, seed: int, ckpt_dir: str,
                 settings: StepSettings, ckpt_every: int, *, mesh=None,
                 state_shardings=None) -> Trainer:
    return Trainer(
        bb,
        adamw.AdamWConfig(lr=1e-4, warmup_steps=2, total_steps=steps),
        DataConfig(vocab=cfg.vocab, seq_len=TRAIN_SEQ,
                   global_batch=TRAIN_BATCH, seed=seed),
        TrainerConfig(total_steps=steps, ckpt_every=ckpt_every,
                      ckpt_dir=ckpt_dir, log_every=steps),
        settings, mesh=mesh, state_shardings=state_shardings)


# --------------------------------------------------------------------------- #
# 2. train                                                                     #
# --------------------------------------------------------------------------- #
def phase_train(seed: int, dev) -> None:
    full, cfg = cut(TRAIN_LAYERS)
    print(f"[train] {ARCH} depth cut from {full.n_layers} to {cfg.n_layers} "
          f"layers; widths kept: d_model {cfg.d_model}, heads "
          f"{cfg.n_heads}/{cfg.n_kv_heads} x {cfg.hd}, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab}; batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, "
          f"{TRAIN_STEPS} steps", flush=True)
    settings = StepSettings()
    bb = Backbone(cfg, remat=settings.remat)   # bf16 compute, fp32 params
    ckpt_dir = tempfile.mkdtemp(prefix="ckpt_", dir=SMOKE_DIR)
    trainer = make_trainer(bb, cfg, TRAIN_STEPS, seed, ckpt_dir, settings,
                           TRAIN_STEPS)
    try:
        state = trainer.init_or_restore(seed=seed)
        n_params = sum(a.size for a in
                       jax.tree_util.tree_leaves(state["params"]))
        print(f"[train] {n_params/1e6:.1f}M params, state "
              f"{tree_bytes(state)/GiB:.2f} GiB on device", flush=True)
        t0 = time.monotonic()
        state = trainer.run(state)
        jax.block_until_ready(state)
        wall = time.monotonic() - t0
        log = trainer.metrics_log
        dts = [m["dt"] for m in log]
        steady = sorted(dts[1:])[len(dts[1:]) // 2]
        print(f"[train] losses {[round(m['loss'], 4) for m in log]}")
        print(f"[train] first step (compile + run) {dts[0]:.2f}s, steady "
              f"step median {steady*1e3:.1f}ms, compile ~"
              f"{dts[0]-steady:.1f}s, run incl. checkpoint {wall:.1f}s, "
              f"peak_bytes_in_use "
              f"{dev.memory_stats()['peak_bytes_in_use']/GiB:.2f} GiB",
              flush=True)

        losses = [m["loss"] for m in log]
        check(all(math.isfinite(x) for x in losses), "every loss is finite")
        ln_v = math.log(cfg.vocab)
        check(abs(losses[0] - ln_v) < 1.0,
              f"first loss {losses[0]:.4f} within 1.0 of ln(vocab) "
              f"{ln_v:.4f}")
        cursor = trainer.store.snapshot(("data_cursor",))
        check(cursor["data_cursor_version"] == TRAIN_STEPS,
              f"data_cursor version {cursor['data_cursor_version']} == "
              f"{TRAIN_STEPS} steps")
        meta = trainer.store.latest_checkpoint()
        check(meta is not None and meta["step"] == TRAIN_STEPS
              and Path(meta["path"]).name == f"step_{TRAIN_STEPS}",
              f"ckpt_meta names the checkpoint: {meta and meta['path']}")
        check(trainer.async_ckpt.errors == [],
              f"async checkpointer errors {trainer.async_ckpt.errors}")
        # the checkpoint's snapshot is the version committed at the last
        # step, which is the state run() returned (nothing donated it since)
        manifest = json.loads(
            (Path(meta["path"]) / "manifest.json").read_text())
        leaf = manifest["leaves"]["params/g0/s0/wq"]
        on_disk = np.load(Path(meta["path"]) / leaf["file"])
        live = np.asarray(state["params"]["g0"]["s0"]["wq"])
        check(on_disk.shape == live.shape and np.array_equal(on_disk, live),
              f"leaf params/g0/s0/wq {list(on_disk.shape)} read back from "
              f"disk equals the snapshot")
    finally:
        trainer.shutdown()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    # the store's cells referenced the params: drop every handle, then look
    del trainer, state, bb
    gc.collect()
    in_use = dev.memory_stats()["bytes_in_use"]
    check(in_use < 0.5 * GiB,
          f"train phase freed: bytes_in_use {in_use/GiB:.3f} GiB")


# --------------------------------------------------------------------------- #
# 3. serve                                                                     #
# --------------------------------------------------------------------------- #
def phase_serve(seed: int, dev) -> None:
    cfg = get_config(ARCH)
    print(f"[serve] {ARCH}: all {cfg.n_layers} layers, bf16 params, "
          f"{SERVE_SLOTS} slots, {SERVE_REQUESTS} requests, prompt "
          f"{PROMPT_LEN}, max_new {MAX_NEW}", flush=True)
    bb = Backbone(cfg, param_dtype=jnp.bfloat16, remat=False)
    params = jax.jit(bb.init)(jax.random.PRNGKey(seed))
    print(f"[serve] params {tree_bytes(params)/GiB:.2f} GiB on device",
          flush=True)
    srv = Server(bb, params, slots=SERVE_SLOTS, ctx=PROMPT_LEN + MAX_NEW)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, PROMPT_LEN,
                                               dtype=np.int32),
                    max_new=MAX_NEW)
            for i in range(SERVE_REQUESTS)]
    for r in reqs:
        srv.submit(r)
    t0 = time.monotonic()
    srv.run()
    wall = time.monotonic() - t0
    print(f"[serve] {srv.stats} in {wall:.1f}s (compiles included); "
          f"peak_bytes_in_use "
          f"{dev.memory_stats()['peak_bytes_in_use']/GiB:.2f} GiB",
          flush=True)
    check(all(r.done.is_set() and len(r.out) == MAX_NEW for r in reqs),
          f"every request finished with {MAX_NEW} tokens: "
          f"{[len(r.out) for r in reqs]}")

    r0 = reqs[0]
    seq = np.concatenate([r0.prompt, np.asarray(r0.out[:-1], np.int32)])
    logits, _ = jax.jit(bb.forward)(params, {"tokens": jnp.asarray(seq[None])})
    ref = np.asarray(logits[0, PROMPT_LEN - 1:, :cfg.vocab], np.float32)
    want = ref.argmax(-1)
    got = np.asarray(r0.out)
    gap = ref.max(-1) - ref[np.arange(MAX_NEW), got]
    print(f"[serve] request 0 tokens {got.tolist()}")
    print(f"[serve] reference argmax {want.tolist()}")
    print(f"[serve] exact {int((got == want).sum())}/{MAX_NEW}, largest "
          f"gap to the reference max {gap.max():.4f} (logit spread "
          f"{ref.std():.3f})", flush=True)
    check(bool((gap <= TIE_TOL).all()),
          f"request 0's greedy tokens are the prefill argmax (ties within "
          f"{TIE_TOL})")
    del srv, params, bb, logits
    gc.collect()


# --------------------------------------------------------------------------- #
# 4. kernels                                                                   #
# --------------------------------------------------------------------------- #
def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _compiled_kernel(name: str, fn, *args) -> None:
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    check("tpu_custom_call" in hlo,
          f"{name}: the compiled program holds a tpu_custom_call")


def _compare(name: str, fn, ref_fn, args, tol: float) -> None:
    got = jax.jit(fn)(*args)
    # the oracle's fp32 contractions at full precision, not the TPU's
    # default single bf16 pass
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ref_fn)(*args)
    for i, (g, w) in enumerate(zip(jax.tree_util.tree_leaves(got),
                                   jax.tree_util.tree_leaves(want))):
        err = _rel_err(g, w)
        check(g.shape == w.shape and err <= tol,
              f"{name}[{i}] {tuple(g.shape)}: max error {err:.2e} of the "
              f"oracle's largest value (limit {tol:.0e})")


def _check_scan(name: str, scan, oracle, args, cots) -> None:
    """Compiled kernel vs oracle: forward, then value and grad of a
    random projection of both outputs."""
    _compiled_kernel(name, scan, *args)
    _compare(f"{name} fwd", scan, oracle, args, 1e-4)

    def loss(fn):
        return lambda *a: sum(jnp.sum(o * c) for o, c in zip(fn(*a), cots))

    # value_and_grad: the backward is the oracle's VJP, so a bare grad
    # would let XLA drop the kernel's unused forward
    argn = tuple(range(len(args)))
    with jax.default_matmul_precision("highest"):
        _compare(f"{name} value_and_grad",
                 jax.value_and_grad(loss(scan), argn),
                 jax.value_and_grad(loss(oracle), argn), args, 1e-4)


def phase_kernels(seed: int) -> None:
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 32))
    normal = lambda shape, dt=jnp.float32: jax.random.normal(
        next(keys), shape, jnp.float32).astype(dt)

    # rwkv6-3b: H=40 heads of 64
    B, T, H, hd = 2, 256, 40, 64
    print(f"[kernels] rwkv6 B={B} T={T} H={H} hd={hd}", flush=True)
    r, k, v = (normal((B, T, H, hd), jnp.bfloat16) for _ in range(3))
    w = jax.nn.sigmoid(normal((B, T, H, hd)) + 2.0)
    args = (r, k, v, w, normal((H, hd)), 0.1 * normal((B, H, hd, hd)))
    _check_scan("rwkv6", ops.rwkv6_scan, kref.rwkv6_scan_ref, args,
                (normal((B, T, H, hd)), normal((B, H, hd, hd))))

    # recurrentgemma-9b: width 4096
    B, T, W = 2, 256, 4096
    print(f"[kernels] rglru B={B} T={T} W={W}", flush=True)
    gate = lambda: jax.nn.sigmoid(normal((B, T, W))).astype(jnp.bfloat16)
    args = (normal((B, T, W), jnp.bfloat16), normal((W,)), gate(), gate(),
            normal((B, W)))
    _check_scan("rglru", ops.rglru_scan, kref.rglru_scan_ref, args,
                (normal((B, T, W)), normal((B, W))))

    # qwen3-4b attention: GQA 32/8, head_dim 128
    B, S, Hq, Hkv, hd = 1, 1024, 32, 8, 128
    print(f"[kernels] flash attention B={B} S={S} heads {Hq}/{Hkv} "
          f"hd={hd} bf16", flush=True)
    args = (normal((B, S, Hq, hd), jnp.bfloat16),
            normal((B, S, Hkv, hd), jnp.bfloat16),
            normal((B, S, Hkv, hd), jnp.bfloat16))
    _compiled_kernel("flash attention", ops.flash_attention, *args)
    _compare("flash fwd", ops.flash_attention, kref.flash_attention_ref,
             args, 2e-2)


# --------------------------------------------------------------------------- #
# --chips 4: the sharded trainer                                               #
# --------------------------------------------------------------------------- #
def _first_loss(layers: int, seed: int, mesh) -> float:
    _, cfg = cut(layers)
    settings = StepSettings()
    if mesh is None:
        bb, st_sh = Backbone(cfg, remat=settings.remat), None
    else:
        bb, p_sh = sharded_backbone(cfg, mesh, TRAIN_BATCH, settings)
        st_sh = train_state_shardings(p_sh, mesh, settings)
    ckpt_dir = tempfile.mkdtemp(prefix="ckpt_", dir=SMOKE_DIR)
    tr = make_trainer(bb, cfg, 1, seed, ckpt_dir, settings, 2, mesh=mesh,
                      state_shardings=st_sh)
    try:
        tr.run(tr.init_or_restore(seed=seed))
        return tr.metrics_log[0]["loss"]
    finally:
        tr.shutdown()
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        del tr, bb
        gc.collect()


def phase_mesh(seed: int) -> None:
    devs = jax.devices()
    mesh = make_mesh((2, 2), ("data", "model"))
    print(f"[mesh] {dict(mesh.shape)} over {len(devs)} x "
          f"{devs[0].device_kind}", flush=True)

    # comparison: the same L=2 first step on one device and on the mesh
    one = _first_loss(COMPARE_LAYERS, seed, None)
    sharded = _first_loss(COMPARE_LAYERS, seed, mesh)
    rel = abs(sharded - one) / abs(one)
    check(rel <= 2e-2, f"L={COMPARE_LAYERS} first-step loss: mesh "
          f"{sharded:.6f} vs one device {one:.6f} (rel {rel:.2e}, "
          f"limit 2e-2)")

    full, cfg = cut(MESH_LAYERS)
    settings = StepSettings()
    print(f"[mesh] {ARCH} depth cut from {full.n_layers} to {MESH_LAYERS} "
          f"layers, full widths; {MESH_STEPS} steps", flush=True)
    bb, p_sh = sharded_backbone(cfg, mesh, TRAIN_BATCH, settings)
    st_sh = train_state_shardings(p_sh, mesh, settings)
    ckpt_dir = tempfile.mkdtemp(prefix="ckpt_", dir=SMOKE_DIR)
    tr = make_trainer(bb, cfg, MESH_STEPS, seed, ckpt_dir, settings,
                      MESH_STEPS + 1, mesh=mesh, state_shardings=st_sh)
    try:
        state = tr.run(tr.init_or_restore(seed=seed))
        jax.block_until_ready(state)
        losses = [m["loss"] for m in tr.metrics_log]
        print(f"[mesh] losses {[round(x, 4) for x in losses]}; step times "
              f"{[round(m['dt'], 3) for m in tr.metrics_log]}s", flush=True)
        check(all(math.isfinite(x) for x in losses), "every loss is finite")
        total = tree_bytes(state)
        # a step also holds fp32 grads: 16 B per parameter in all
        need = total + tree_bytes(state["params"])
        per_dev = {d.id: 0 for d in devs}
        for leaf in jax.tree_util.tree_leaves(state):
            for sh in leaf.addressable_shards:
                per_dev[sh.device.id] += sh.data.nbytes
        limit = devs[0].memory_stats()["bytes_limit"]
        print(f"[mesh] state {total/GiB:.2f} GiB, with grads "
              f"{need/GiB:.2f} GiB; one chip holds {limit/GiB:.2f} GiB",
              flush=True)
        for d in devs:
            ms = d.memory_stats()
            print(f"[mesh] device {d.id}: state shards "
                  f"{per_dev[d.id]/GiB:.2f} GiB, bytes_in_use "
                  f"{ms['bytes_in_use']/GiB:.2f} GiB, peak "
                  f"{ms['peak_bytes_in_use']/GiB:.2f} GiB", flush=True)
        check(need > limit, "state and grads do not fit one chip")
        check(all(0.15 * total < per_dev[d.id] < 0.35 * total
                  for d in devs),
              "every device holds 15-35% of the state")
        check(all(d.memory_stats()["bytes_in_use"] > 0.15 * total
                  for d in devs), "every device's bytes_in_use carries "
              "its share")
    finally:
        tr.shutdown()
        shutil.rmtree(ckpt_dir, ignore_errors=True)


# --------------------------------------------------------------------------- #
def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()
    cache = enable_compile_cache()

    devs = jax.devices()
    dev = devs[0]
    print(f"[device] platform {dev.platform}, kind {dev.device_kind}, "
          f"count {len(devs)}", flush=True)
    if dev.platform != "tpu":
        print("[device] no TPU: this smoke runs on the chip only",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"[device] --chips {args.chips} needs {args.chips} devices",
              file=sys.stderr)
        return 2
    print(f"[device] compile cache {cache}", flush=True)
    SMOKE_DIR.mkdir(exist_ok=True)

    try:
        if args.chips == 4:
            phase_mesh(args.seed)
        else:
            phase_train(args.seed, dev)
            phase_serve(args.seed, dev)
            phase_kernels(args.seed)
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
