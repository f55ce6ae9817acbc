"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

Run it as a fresh process (``python -m repro.launch.dryrun``): ``main()``
adds ``--xla_force_host_platform_device_count=512`` to ``XLA_FLAGS`` before
jax initializes its backend, so the host platform exposes 512 placeholder
devices for the production meshes. In-process callers of ``run_cell`` set
``XLA_FLAGS`` themselves before touching jax.

For each cell this:
  1. builds the arch's Backbone with the production PartitionPlan,
  2. constructs ShapeDtypeStruct input specs (no allocation),
  3. ``jax.jit(step, in_shardings, out_shardings).lower(...).compile()``,
  4. records memory_analysis / cost_analysis / parsed collective bytes
     into results/dryrun/<cell>.json (incremental; --force to redo).

``long_500k`` is skipped for pure-full-attention archs (see DESIGN.md §4)
and recorded as {"skipped": reason}.
"""
import argparse
import json
import os
import time
import traceback
from pathlib import Path
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch import hlocost
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh, tp_size
from repro.launch.shardings import (batch_shardings, cache_shardings,
                                    effective_dp, sharded_backbone,
                                    train_state_shardings)
from repro.models import SHAPES, Backbone, PartitionPlan, get_config
from repro.models.config import ARCH_NAMES, ShapeConfig
from repro.optim import adamw
from repro.runtime.steps import (StepSettings, make_decode_step,
                                 make_prefill_step, make_train_step,
                                 train_state_specs)

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

# long_500k policy (DESIGN.md §4): run only where the KV footprint is bounded
LONG_OK = {"rwkv6-3b", "mixtral-8x22b", "recurrentgemma-9b"}


def cell_skip_reason(arch: str, shape: ShapeConfig) -> Optional[str]:
    if shape.name == "long_500k" and arch not in LONG_OK:
        return ("full-attention KV cache at 524288 would be unbounded; "
                "sub-quadratic archs only (DESIGN.md §4)")
    return None


def _spec_like(tree, shardings):
    return jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        tree, shardings)


def build_cell(arch: str, shape: ShapeConfig, mesh, *,
               settings: StepSettings):
    """Returns (jitted_fn, example_args_specs)."""
    cfg = get_config(arch)
    serve = shape.kind != "train"
    bb, p_sh = sharded_backbone(cfg, mesh, shape.global_batch, settings,
                                serve=serve)
    dp = effective_dp(cfg, mesh, shape.global_batch)
    B, S = shape.global_batch, shape.seq_len
    bsh = batch_shardings(cfg, shape, mesh, batch_sharded=B > 1)
    dpspec = (dp or None) if B > 1 else None

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig()
        step = make_train_step(bb, opt_cfg, settings)
        state_specs = train_state_specs(bb, settings)
        state_sh = train_state_shardings(p_sh, mesh, settings)
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        if cfg.is_enc_dec:
            batch["enc_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        jfn = jax.jit(step, in_shardings=(state_sh, bsh),
                      donate_argnums=(0,))
        args = (_spec_like(state_specs, state_sh),
                _spec_like(batch, bsh))
        return jfn, args

    param_specs = bb.param_specs()
    if shape.kind == "prefill":
        step = make_prefill_step(bb, ctx=S)
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.is_enc_dec:
            batch["enc_frames"] = jax.ShapeDtypeStruct(
                (B, cfg.enc_seq, cfg.d_model), jnp.bfloat16)
        jfn = jax.jit(step, in_shardings=(p_sh, bsh))
        return jfn, (_spec_like(param_specs, p_sh), _spec_like(batch, bsh))

    # decode
    step = make_decode_step(bb)
    c_sh = cache_shardings(bb, mesh, B)
    cache_specs = jax.eval_shape(lambda: bb.init_cache(B, S))
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = NamedSharding(mesh, P(dpspec, None))
    jfn = jax.jit(step, in_shardings=(p_sh, c_sh, tok_sh),
                  donate_argnums=(1,))
    return jfn, (_spec_like(param_specs, p_sh),
                 _spec_like(cache_specs, c_sh),
                 jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh))


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             settings: StepSettings, verbose: bool = True) -> Dict[str, Any]:
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_chips = 1
    for a in mesh.axis_names:
        n_chips *= mesh.shape[a]
    t0 = time.time()
    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "chips": n_chips, "settings": settings.__dict__,
    }
    reason = cell_skip_reason(arch, shape)
    if reason:
        result["skipped"] = reason
        return result
    with mesh:
        jfn, args = build_cell(arch, shape, mesh, settings=settings)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    totals = hlocost.analyze(hlo)       # trip-count-aware (source of record)

    cfg = get_config(arch)
    plan = PartitionPlan(tp=tp_size(mesh))
    bb = Backbone(cfg, plan)
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    mflops = rl.model_flops(bb, shape.kind, tokens)
    terms = rl.derive_terms_from_totals(totals, mflops, n_chips,
                                        rl.chip_peaks(rl.DRYRUN_TARGET))

    result.update({
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        },
        "cost_analysis_raw": {"flops": cost.get("flops"),
                              "bytes_accessed": cost.get("bytes accessed")},
        "hlocost": totals.to_json(),
        "roofline": terms.to_json(),
    })
    if verbose:
        m = result["memory"]
        print(f"[{arch} × {shape_name} × {mesh_kind}] "
              f"compile={t_compile:.1f}s "
              f"peak/dev={(m['peak_bytes'] or 0)/2**30:.2f}GiB "
              f"flops/dev={terms.hlo_flops:.3e} "
              f"coll/dev={totals.collective_bytes/2**20:.1f}MiB "
              f"(in-loop {totals.in_loop_count:.0f} ops) "
              f"dominant={terms.dominant} "
              f"frac={terms.roofline_fraction:.3f}",
              flush=True)
    return result


def main() -> None:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=512").strip()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all'")
    ap.add_argument("--shape", default="all",
                    help=f"one of {list(SHAPES)} or 'all'")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--zero3", type=int, default=1)
    ap.add_argument("--gather-weights", type=int, default=1)
    ap.add_argument("--remat", type=int, default=1)
    ap.add_argument("--compress-grads", type=int, default=0)
    ap.add_argument("--moe-ep", type=int, default=1)
    ap.add_argument("--remat-policy", default="full", choices=["full", "dots"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--tag", default="", help="suffix for result files")
    args = ap.parse_args()

    archs = ARCH_NAMES if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    settings = StepSettings(zero3=bool(args.zero3),
                            gather_weights=bool(args.gather_weights),
                            remat=bool(args.remat),
                            compress_grads=bool(args.compress_grads),
                            remat_policy=args.remat_policy,
                            moe_ep=bool(args.moe_ep),
                            microbatches=args.microbatches)
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)

    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh_kind in meshes:
                tag = f"-{args.tag}" if args.tag else ""
                out = RESULTS_DIR / f"{arch}--{shape}--{mesh_kind}{tag}.json"
                if out.exists() and not args.force:
                    print(f"skip (exists): {out.name}", flush=True)
                    continue
                try:
                    res = run_cell(arch, shape, mesh_kind, settings=settings)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((arch, shape, mesh_kind, repr(e)))
                    res = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "error": repr(e)}
                out.write_text(json.dumps(res, indent=2))
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nDRY-RUN COMPLETE: all requested cells lowered & compiled.")


if __name__ == "__main__":
    main()
