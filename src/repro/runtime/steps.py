"""Step functions: train_step / prefill_step / decode_step builders.

These are the functions the dry-run lowers with ``.lower().compile()`` for
every (architecture × shape × mesh) cell, and the train loop executes for
the end-to-end example.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.models.backbone import REMAT_POLICIES, Backbone
from repro.optim import adamw

Params = Any


@dataclass(frozen=True)
class StepSettings:
    """Schedule/memory knobs — the §Perf hillclimb levers."""

    zero3: bool = True          # ZeRO-3 "data"-sharded parameters
    gather_weights: bool = True  # per-layer weight all-gather in the scan body
    remat: bool = True
    # a name in models.backbone.REMAT_POLICIES, or None: the trainer takes
    # the first of them whose compiled step fits (compile_train_step)
    remat_policy: Optional[str] = None
    compress_grads: bool = False
    moe_ep: bool = True         # expert-parallel MoE via shard_map (§Perf)
    microbatches: int = 1       # gradient accumulation: divides the saved-
    # activation peak by k at the cost of k sequential sub-steps


def make_train_step(bb: Backbone, opt_cfg: adamw.AdamWConfig,
                    settings: StepSettings = StepSettings()
                    ) -> Callable:
    """(state, batch) -> (state, metrics); state = {params, opt, error?}.
    The layers are checkpointed under ``settings.remat_policy``, or fully
    where it names none."""
    policy = settings.remat_policy or "full"
    if policy not in REMAT_POLICIES:
        raise ValueError(f"remat policy {policy!r}: one of "
                         f"{list(REMAT_POLICIES)}")

    def scoped_train_step(state: Dict[str, Any],
                          batch: Dict[str, jax.Array]):
        # the two scopes name the step's phases in a device trace: every
        # op of the forward and backward carries "loss" in its op name,
        # every op of the update "optimizer". The persistent compile cache
        # keys a program by its name and its HLO without op names, so a
        # step of the same name built before the scopes would be loaded in
        # its place and carry none: the step is named apart from it.
        with jax.named_scope("loss"):
            loss, grads = loss_and_grads(state["params"], batch)
        if settings.compress_grads:
            grads, err = adamw.compress_with_feedback(grads, state["error"])
        with jax.named_scope("optimizer"):
            new_params, new_opt, metrics = adamw.apply_updates(
                opt_cfg, state["params"], state["opt"], grads)
        new_state = {"params": new_params, "opt": new_opt}
        if settings.compress_grads:
            new_state["error"] = err
        metrics = dict(metrics, loss=loss)
        return new_state, metrics

    def loss_and_grads(params, batch):
        k = settings.microbatches
        if k > 1:
            # gradient accumulation: scan over k microbatch slices; the
            # backward's saved-activation stack shrinks by k (the lever
            # that keeps big-batch cells inside HBM at scale)
            def slice_mb(i, a):
                mb = a.shape[0] // k
                return jax.lax.dynamic_slice_in_dim(a, i * mb, mb, axis=0)

            def mb_body(carry, i):
                acc, loss_acc = carry
                mb = jax.tree_util.tree_map(lambda a: slice_mb(i, a), batch)
                l, g = jax.value_and_grad(
                    lambda p: bb.loss_fn(p, mb, policy))(params)
                acc = jax.tree_util.tree_map(jnp.add, acc, g)
                return (acc, loss_acc + l), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss), _ = jax.lax.scan(
                mb_body, (zeros, jnp.zeros((), jnp.float32)),
                jnp.arange(k))
            grads = jax.tree_util.tree_map(lambda g: g / k, grads)
            return loss / k, grads
        return jax.value_and_grad(
            lambda p: bb.loss_fn(p, batch, policy))(params)

    return scoped_train_step


def memory_limit(args) -> Optional[int]:
    """Bytes a program run on ``args`` may take on each device ``args``
    live on, at most: the device's ``bytes_limit``, less what it holds
    besides ``args`` (``bytes_in_use`` counts them). The least over the
    devices; None where a device has no limit to read (the CPU)."""
    held: Dict[Any, int] = {}
    for leaf in jax.tree_util.tree_leaves(args):
        for shard in getattr(leaf, "addressable_shards", ()):
            held[shard.device] = held.get(shard.device, 0) \
                + shard.data.nbytes
    room = []
    for device, own in held.items():
        stats = device.memory_stats() or {}
        if "bytes_limit" not in stats:
            return None
        room.append(stats["bytes_limit"] - stats.get("bytes_in_use", 0)
                    + own)
    return min(room, default=None)


def compile_train_step(build: Callable[[Optional[str]], Any],
                       policies: Sequence[Optional[str]],
                       args: Tuple[Any, ...], limit: Optional[int]
                       ) -> Tuple[Any, Optional[str]]:
    """Lower and compile ``build(policy)`` (a jitted train step) at
    ``args`` under each of ``policies`` in turn, and keep the first the
    device can hold: one the compiler did not refuse for memory, whose
    peak is at most ``limit`` bytes (any peak where ``limit`` is None). The
    last is kept whatever its peak. Returns (the compiled step, its
    policy)."""
    for i, policy in enumerate(policies):
        last = i == len(policies) - 1
        try:
            compiled = build(policy).lower(*args).compile()
        except jax.errors.JaxRuntimeError as e:
            if last or "RESOURCE_EXHAUSTED" not in str(e):
                raise
            continue
        peak = compiled.memory_analysis().peak_memory_in_bytes
        if last or limit is None or peak <= limit:
            return compiled, policy


def init_train_state(bb: Backbone, key: jax.Array,
                     settings: StepSettings = StepSettings()) -> Dict[str, Any]:
    params = bb.init(key)
    state = {"params": params, "opt": adamw.init_state(params)}
    if settings.compress_grads:
        state["error"] = jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, a.dtype), params)
    return state


def train_state_specs(bb: Backbone,
                      settings: StepSettings = StepSettings()) -> Any:
    return jax.eval_shape(lambda k: init_train_state(bb, k, settings),
                          jax.random.PRNGKey(0))


def make_prefill_step(bb: Backbone, ctx: int) -> Callable:
    def prefill_step(params, batch):
        return bb.prefill(params, batch, ctx)

    return prefill_step


def make_decode_step(bb: Backbone) -> Callable:
    def decode_step(params, cache, tokens):
        return bb.decode_step(params, cache, tokens)

    return decode_step
