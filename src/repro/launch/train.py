"""Training launcher: config + mesh + trainer wiring.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-4b --reduced \\
        --steps 50 --batch 8 --seq 128

Computes in bf16 over fp32 master parameters, with parameters, optimizer
state and batch sharded over a mesh. ``--mesh host`` (the default) spans
every device of this process as ``(data, model)``, with ``model`` 2 wide
when the device count is even (one device: a 1x1 mesh). ``--mesh
production`` and ``--mesh multipod`` build the 256- and 512-chip meshes of
``launch.mesh``; the process must already see that many devices. The run
exits non-zero if any checkpoint failed to reach disk.
"""
from __future__ import annotations

import argparse

import jax

from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.launch.shardings import sharded_backbone, train_state_shardings
from repro.models import get_config, reduced
from repro.optim import adamw
from repro.runtime.steps import StepSettings
from repro.runtime.train_loop import Trainer, TrainerConfig


def build_mesh(kind: str):
    if kind == "host":
        n = jax.device_count()
        tp = 2 if n % 2 == 0 else 1
        return make_host_mesh(dp=n // tp, tp=tp)
    return make_production_mesh(multi_pod=(kind == "multipod"))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced smoke config (CPU-sized)")
    ap.add_argument("--mesh", default="host",
                    choices=["host", "production", "multipod"])
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--zero3", type=int, default=0)
    ap.add_argument("--remat", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    settings = StepSettings(zero3=bool(args.zero3), gather_weights=bool(args.zero3),
                            remat=bool(args.remat), moe_ep=False)
    mesh = build_mesh(args.mesh)
    bb, p_sh = sharded_backbone(cfg, mesh, args.batch, settings)
    state_sh = train_state_shardings(p_sh, mesh, settings)
    n = sum(x.size for x in jax.tree_util.tree_leaves(
        jax.eval_shape(bb.init, jax.random.PRNGKey(0))))
    print(f"[launch] {cfg.name}: {n/1e6:.1f}M params, "
          f"{jax.device_count()} devices, mesh {args.mesh}")

    trainer = Trainer(
        bb,
        adamw.AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5 + 1),
                          total_steps=args.steps),
        DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                   global_batch=args.batch,
                   enc_seq=cfg.enc_seq, enc_dim=cfg.d_model),
        TrainerConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, log_every=10),
        settings, mesh=mesh, state_shardings=state_sh)
    try:
        state = trainer.init_or_restore()
        trainer.run(state)
        log = trainer.metrics_log
        print(f"[launch] done: loss {log[0]['loss']:.4f} -> "
              f"{log[-1]['loss']:.4f}; checkpoints {trainer.async_ckpt.saved}")
    finally:
        trainer.shutdown()


if __name__ == "__main__":
    main()
