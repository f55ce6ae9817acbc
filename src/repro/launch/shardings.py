"""Sharding rules: parameter specs, batch/cache specs, activation sharder.

Parameter policy (see DESIGN.md §5):

* TP ("model" axis): attention head dims, FFN hidden dim, expert dim (EP)
  when divisible, vocab dim of embeddings.
* ZeRO ("data" axis): the non-TP matrix dim of every large 2-D kernel.
  With ``zero3=True`` parameters themselves are sharded over "data" —
  the backward pass then reduce-scatters each layer's gradient *inside*
  the layer scan (the OptSVA-CF "early release on last write" schedule).
  With ``zero3=False`` parameters are replicated over "data" and the
  gradient all-reduce happens once after the backward scan ("release at
  commit", the SVA-like baseline). Both lower; §Perf compares them.
* "pod" axis: pure DP — parameters replicated, batch sharded.

Everything is name/shape-pattern based over the backbone's parameter tree,
so new layer kinds only need a rule here if they introduce new leaf names.
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.backbone import Backbone
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.partition import PartitionPlan
from .mesh import dp_axes, tp_size

Params = Any


def _divisible(n: int, m: int) -> bool:
    return m > 0 and n % m == 0


def full_dp_arch(cfg: ModelConfig) -> bool:
    """Attention-free (SSM) archs get nothing from tensor parallelism but
    per-layer activation all-reduces (tiny per-layer matmuls, low arithmetic
    intensity). For them the "model" axis is repurposed as additional data
    parallelism: batch sharded over data×model, weights ZeRO-sharded over
    both and gathered per layer (the early-release prefetch) — measured 13×
    lower collective volume on rwkv6 train_4k (EXPERIMENTS.md §Perf)."""
    return cfg.family == "ssm"


def param_spec(path: Tuple[str, ...], shape: Tuple[int, ...],
               cfg: ModelConfig, mesh: Mesh, *, zero3: bool = True,
               full_dp: bool = False) -> P:
    """PartitionSpec for one parameter leaf, by name pattern + shape."""
    spec = _param_spec_raw(path, shape, cfg, mesh, zero3=zero3)
    if full_dp:
        spec = P(*(None if s == "model" else s for s in spec))
    return spec


def _param_spec_raw(path: Tuple[str, ...], shape: Tuple[int, ...],
                    cfg: ModelConfig, mesh: Mesh, *, zero3: bool = True) -> P:
    name = path[-1]
    tp = tp_size(mesh)
    zaxis = "data" if zero3 else None

    def zshard(dim: int) -> Optional[str]:
        return zaxis if _divisible(shape[dim], mesh.shape.get("data", 1)) else None

    # ---- embeddings / head ---------------------------------------------------
    if name == "tok":                       # [Vp, D]
        if not cfg.tie_embeddings:
            # untied: vocab over "data" (ZeRO) + D over "model" — the lookup
            # all-reduce then runs on the model-sharded (16x smaller) output
            return P(zshard(0), "model")
        return P("model", zshard(1))
    if name == "enc_pos":                   # [enc_seq, D]
        return P(None, None)
    if name == "lm_head":                   # [D, Vp]
        return P(zshard(0), "model")
    if name == "final_norm":
        return P(None)

    # ---- stacked layer leaves: shape[0] is the repeat axis -------------------
    if len(shape) == 4 and name in ("w_gate", "w_up", "w_down") \
            and cfg.ffn_kind == "moe":
        # experts [R, E|V, D, Fe] / [R, E|V, Fe, D]; the EP path stores
        # virtualized experts whose dim-1 always divides tp
        if _divisible(shape[1], tp):
            return P(None, "model", zshard(2), None)
        # TP inside the expert instead (GSPMD baseline, few big experts)
        if name == "w_down":
            return P(None, None, "model", zshard(3))
        return P(None, None, zshard(2), "model")
    if name == "router":                    # [R, D, E]
        return P(None, zshard(1), None)
    if name in ("wq", "wk", "wv", "c_wq", "c_wk", "c_wv",
                "w_r", "w_k", "w_v", "w_g"):
        return P(None, zshard(1), "model")  # [R, D, out]
    if name in ("wo", "c_wo", "w_o"):
        return P(None, "model", zshard(2))  # [R, out, D]
    if name in ("w_gate", "w_up", "w_in", "w_gate_branch"):
        return P(None, zshard(1), "model")  # [R, D, F/W]
    if name in ("w_down", "w_out"):
        return P(None, "model", zshard(2))  # [R, F/W, D]
    if name == "w_rgate":                   # [R, D, D]
        return P(None, zshard(1), "model")
    if name in ("bq", "bk", "bv", "c_bq", "c_bk", "c_bv",
                "u", "w0", "ln_x", "conv_b", "gb_a", "gb_x", "a_log"):
        return P(None, "model") if _divisible(shape[1], tp) else P(None, None)
    if name == "conv_w":                    # [R, K, W]
        return P(None, None, "model")
    if name in ("gw_a", "gw_x"):            # [R, NB, wb, wb]
        return P(None, "model", None, None) if _divisible(shape[1], tp) \
            else P(None, None, None, None)
    if name in ("wd_a", "dd_a"):            # [R, D, r]
        return P(None, zshard(1), None)
    if name == "wd_b":                      # [R, r, Dr]
        return P(None, None, "model")
    if name.startswith("dd_b"):             # [R, 32, D]
        return P(None, None, zshard(2))
    # norms, mu_*, small vectors -> replicated
    return P(*([None] * len(shape)))


def param_shardings(bb: Backbone, mesh: Mesh, *, zero3: bool = True,
                    full_dp: bool = False) -> Params:
    specs = bb.param_specs()

    def to_sharding(path, leaf):
        names = tuple(
            p.key if hasattr(p, "key") else str(p) for p in path)
        spec = param_spec(names, leaf.shape, bb.cfg, mesh, zero3=zero3,
                          full_dp=full_dp)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(to_sharding, specs)


# --------------------------------------------------------------------------- #
# Batches and caches                                                           #
# --------------------------------------------------------------------------- #
def batch_spec(mesh: Mesh) -> P:
    return P(dp_axes(mesh) or None)


def full_dp_active(cfg: ModelConfig, mesh: Mesh, global_batch: int) -> bool:
    """full-DP applies only when the batch divides the whole device grid."""
    if not full_dp_arch(cfg):
        return False
    total = 1
    for a in dp_axes(mesh) + ("model",):
        total *= mesh.shape[a]
    return _divisible(global_batch, total)


def effective_dp(cfg: ModelConfig, mesh: Mesh, global_batch: int
                 ) -> Tuple[str, ...]:
    """Batch-sharding axes: data(+pod); plus 'model' for full-DP archs
    when the batch divides the larger grid."""
    dp = dp_axes(mesh)
    if full_dp_active(cfg, mesh, global_batch):
        return dp + ("model",)
    return dp


def batch_shardings(cfg: ModelConfig, shape: ShapeConfig, mesh: Mesh,
                    *, batch_sharded: bool = True) -> Dict[str, NamedSharding]:
    dp = effective_dp(cfg, mesh, shape.global_batch) if batch_sharded else ()
    tok = NamedSharding(mesh, P(dp or None, None))
    out = {"tokens": tok}
    if shape.kind == "train":
        out["labels"] = tok
    if cfg.is_enc_dec:
        out["enc_frames"] = NamedSharding(mesh, P(dp or None, None, None))
    return out


def cache_shardings(bb: Backbone, mesh: Mesh, B: int) -> Params:
    """Cache specs: batch over dp (when divisible), heads/width over model."""
    cache_shape = jax.eval_shape(lambda: bb.init_cache(B, 8))
    dp = dp_axes(mesh)
    dp_total = 1
    for a in dp:
        dp_total *= mesh.shape[a]
    bshard = dp if _divisible(B, dp_total) else None
    tp = tp_size(mesh)

    def spec_for(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        shp = leaf.shape
        if name == "pos":
            return NamedSharding(mesh, P())
        if name == "kpos":
            return NamedSharding(mesh, P(None, None))
        if name in ("k", "v", "ck", "cv"):    # [R, B, C, KV, hd]
            kv = "model" if _divisible(shp[3], tp) else None
            return NamedSharding(mesh, P(None, bshard, None, kv, None))
        if name == "wkv":                     # [R, B, H, hd, hd]
            h = "model" if _divisible(shp[2], tp) else None
            return NamedSharding(mesh, P(None, bshard, h, None, None))
        if name in ("shift1", "shift2"):      # [R, B, D]
            return NamedSharding(mesh, P(None, bshard, None))
        if name == "conv":                    # [R, B, K-1, W]
            w = "model" if _divisible(shp[3], tp) else None
            return NamedSharding(mesh, P(None, bshard, None, w))
        if name == "h":                       # [R, B, W]
            w = "model" if _divisible(shp[2], tp) else None
            return NamedSharding(mesh, P(None, bshard, w))
        return NamedSharding(mesh, P(*([None] * len(shp))))

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def sharded_backbone(cfg: ModelConfig, mesh: Mesh, global_batch: int,
                     settings, *, serve: bool = False
                     ) -> Tuple[Backbone, Params]:
    """The Backbone wired for ``mesh`` and its parameter shardings.

    Training keeps fp32 master parameters and computes in bf16; serving
    holds bf16 parameters. ``settings`` is a ``runtime.steps.StepSettings``.
    """
    fdp = full_dp_active(cfg, mesh, global_batch)
    plan = PartitionPlan(tp=1 if fdp else tp_size(mesh))
    dp = effective_dp(cfg, mesh, global_batch)
    gatherer = (make_param_gatherer(cfg, mesh, full_dp=fdp)
                if (settings.gather_weights and settings.zero3
                    and not serve) else None)
    bb = Backbone(cfg, plan,
                  compute_dtype=jnp.bfloat16,
                  param_dtype=jnp.bfloat16 if serve else jnp.float32,
                  remat=settings.remat and not serve,
                  sharder=make_sharder(cfg, mesh,
                                       batch_sharded=global_batch > 1,
                                       global_batch=global_batch),
                  param_gather=gatherer,
                  moe_impl="ep" if settings.moe_ep else "gspmd",
                  mesh=mesh,
                  dp_axes=dp if global_batch > 1 else ())
    return bb, param_shardings(bb, mesh, zero3=settings.zero3, full_dp=fdp)


def train_state_shardings(p_sh: Params, mesh: Mesh, settings) -> Params:
    """Shardings of ``runtime.steps.init_train_state``'s tree: the
    optimizer moments (and error feedback) follow their parameters."""
    state_sh = {"params": p_sh,
                "opt": {"step": NamedSharding(mesh, P()),
                        "m": p_sh, "v": p_sh}}
    if settings.compress_grads:
        state_sh["error"] = p_sh
    return state_sh


def make_param_gatherer(cfg: ModelConfig, mesh: Mesh, *,
                        full_dp: bool = False) -> Callable:
    """Per-layer weight-gather constraint for the scan body.

    Under ZeRO-3 ("data"-sharded weights), constraining the *sliced* layer
    parameters to their TP-only sharding inside the scan body makes GSPMD
    all-gather each layer's weights right before use (prefetch — the
    paper's asynchronous read-only buffering) and reduce-scatter each
    layer's gradient right after its backward (early release on last
    write), instead of all-reducing activations at every matmul whose
    contraction dim is "data"-sharded.
    """

    def gather(layer_params: Params) -> Params:
        def one(path, leaf):
            names = tuple(p.key if hasattr(p, "key") else str(p) for p in path)
            # rules index shapes with the stacked dim first; re-add it
            spec = param_spec(names, (1,) + leaf.shape, cfg, mesh,
                              zero3=False, full_dp=full_dp)
            sliced = P(*spec[1:]) if len(spec) > 1 else P()
            if len(sliced) != leaf.ndim:
                return leaf
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, sliced))

        return jax.tree_util.tree_map_with_path(one, layer_params)

    return gather


# --------------------------------------------------------------------------- #
# Activation sharder                                                           #
# --------------------------------------------------------------------------- #
def make_sharder(cfg: ModelConfig, mesh: Mesh,
                 *, batch_sharded: bool = True,
                 global_batch: int = 0) -> Callable:
    dp = (effective_dp(cfg, mesh, global_batch or 1 << 30)
          if batch_sharded else ())
    dps = dp or None
    tp = tp_size(mesh)
    ep = cfg.ffn_kind == "moe" and _divisible(cfg.n_experts, tp)
    fdp = batch_sharded and full_dp_active(cfg, mesh, global_batch or 1 << 30)

    rules: Dict[str, P] = {
        "act_hidden": P(dps, None, None),
        "act_heads": P(dps, None, None if fdp else "model"),
        "logits": P(dps, None, None if fdp else "model"),
        "moe_buf": P("model", None, None) if ep else P(None, None, "model"),
    }

    def shard(x: jax.Array, name: str) -> jax.Array:
        spec = rules.get(name)
        if spec is None or len(spec) != x.ndim:
            # unknown tag or rank mismatch (e.g. decode-step edge): no-op
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))

    return shard
