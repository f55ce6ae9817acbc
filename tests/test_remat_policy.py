"""What the layer scan's checkpoint keeps for the backward.

Under the saving policy (``"dots"``) the backward reads the layer's
projection outputs instead of recomputing them: the gradients are those of
full remat, the gradient's matmul work falls by exactly the recomputed
projections, and the trainer keeps that policy only where the compiled step
fits the device, falling back to full remat where it does not.
"""
import functools
import math
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr
from jax.extend.core.primitives import scan_p

from repro.data.pipeline import DataConfig, make_batch
from repro.models import Backbone, LayerGroup, ModelConfig
from repro.obs import compiles, metrics
from repro.optim import adamw
from repro.models.backbone import REMAT_POLICIES
from repro.runtime import profiling, train_loop
from repro.runtime.steps import (StepSettings, compile_train_step,
                                 init_train_state, make_train_step,
                                 memory_limit)
from repro.runtime.train_loop import Trainer, TrainerConfig

# a tiny qwen3: qk-norm, GQA, SwiGLU, tied head; every width distinct, so a
# residual's width says which projection made it
D, H, KV, HD, F, L = 40, 4, 2, 12, 72, 3
TINY = ModelConfig(name="remat-test", family="dense", d_model=D, n_heads=H,
                   n_kv_heads=KV, head_dim=HD, d_ff=F, vocab=128,
                   groups=(LayerGroup(("attn",), L),), qk_norm=True,
                   ffn_kind="swiglu", tie_embeddings=True)
B, S = 2, 16
T = B * S
SETTINGS = StepSettings(zero3=False, gather_weights=False)


def _loss(policy):
    """The tiny model's loss, its layers checkpointed under ``policy``
    (None: not checkpointed)."""
    bb = Backbone(TINY, compute_dtype=jnp.float32, remat=policy is not None)
    return functools.partial(bb.loss_fn, remat_policy=policy or "full")


def _batch(seed=0):
    return make_batch(DataConfig(vocab=TINY.vocab, seq_len=S,
                                 global_batch=B, seed=seed), 0)


def _matmul_flops(jaxpr: Jaxpr, times: int = 1) -> int:
    """dot_general FLOPs of ``jaxpr``, scan bodies times their length."""
    total = 0
    for e in jaxpr.eqns:
        if e.primitive.name == "dot_general":
            (contract, _), _ = e.params["dimension_numbers"]
            lhs = e.invars[0].aval.shape
            total += times * 2 * math.prod(e.outvars[0].aval.shape) \
                * math.prod(lhs[i] for i in contract)
        inner = times * e.params["length"] if e.primitive is scan_p else times
        for v in e.params.values():
            for x in v if isinstance(v, (tuple, list)) else (v,):
                if isinstance(x, ClosedJaxpr):
                    total += _matmul_flops(x.jaxpr, inner)
                elif isinstance(x, Jaxpr):
                    total += _matmul_flops(x, inner)
    return total


def test_saving_policy_gives_full_remats_gradients():
    params = Backbone(TINY).init(jax.random.PRNGKey(0))
    batch = _batch()
    grads = {p: jax.jit(jax.grad(_loss(p)))(params, batch)
             for p in ("full", "dots")}
    for a, b in zip(jax.tree_util.tree_leaves(grads["full"]),
                    jax.tree_util.tree_leaves(grads["dots"])):
        a, b = np.asarray(a), np.asarray(b)
        assert np.max(np.abs(a - b)) <= 1e-5 * np.max(np.abs(a))


def test_saving_policy_removes_exactly_the_projections_recompute():
    """Full remat recomputes every projection but the last (down), whose
    output no gradient reads; the saving policy recomputes none of them,
    and leaves only the attention's own forward to be done twice."""
    params = jax.eval_shape(Backbone(TINY).init, jax.random.PRNGKey(0))
    batch = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), _batch())
    flops = {p: _matmul_flops(jax.make_jaxpr(jax.grad(_loss(p)))(
        params, batch).jaxpr) for p in ("full", "dots", None)}
    q, kv, o, gate_up = D * H * HD, 2 * D * KV * HD, H * HD * D, 2 * D * F
    assert flops["full"] - flops["dots"] == L * 2 * T * (q + kv + o + gate_up)
    assert 0 < flops["dots"] - flops[None] < L * 2 * T * q


def _jit_builder(bb):
    return lambda policy: jax.jit(
        make_train_step(bb, adamw.AdamWConfig(),
                        StepSettings(zero3=False, gather_weights=False,
                                     remat_policy=policy)),
        donate_argnums=(0,))


def _args(bb):
    return init_train_state(bb, jax.random.PRNGKey(0)), _batch()


def _temp_bytes(build, args, policy):
    return build(policy).lower(*args).compile().memory_analysis() \
        .temp_size_in_bytes


@pytest.mark.parametrize("room,kept", [(None, "dots"), (0, "dots"),
                                       (-1, "full")],
                         ids=["no-limit", "peak-at-limit", "peak-over-limit"])
def test_the_saving_policy_is_kept_only_where_the_step_fits(room, kept):
    bb = Backbone(TINY, compute_dtype=jnp.float32)
    build, args = _jit_builder(bb), _args(bb)
    peak = build("dots").lower(*args).compile().memory_analysis() \
        .peak_memory_in_bytes
    limit = None if room is None else peak + room
    compiled, policy = compile_train_step(build, tuple(REMAT_POLICIES),
                                          args, limit)
    assert policy == kept
    assert compiled.memory_analysis().temp_size_in_bytes \
        == _temp_bytes(build, args, kept)
    _, out = compiled(*_args(bb))
    assert np.isfinite(float(out["loss"]))


def test_the_saving_policy_holds_more_temporaries_than_full_remat():
    """The saved projections live in the step's temporaries: the saving
    step holds more of them than full remat's, the price of its fit test."""
    bb = Backbone(TINY, compute_dtype=jnp.float32)
    build, args = _jit_builder(bb), _args(bb)
    assert _temp_bytes(build, args, "dots") > _temp_bytes(build, args,
                                                          "full")


class _Device:
    """A device with a memory limit and some of it in use."""

    def __init__(self, limit, in_use):
        self.stats = {"bytes_limit": limit, "bytes_in_use": in_use}

    def memory_stats(self):
        return self.stats


def _leaf(*shards):
    """An array with one shard of ``nbytes`` on each ``device``."""
    return SimpleNamespace(addressable_shards=[
        SimpleNamespace(device=d, data=SimpleNamespace(nbytes=n))
        for d, n in shards])


def test_memory_limit_leaves_out_what_the_device_holds_besides_the_args():
    """Room for a step is the limit less what is in use, but for the
    step's own arguments: another trainer's state, an evaluator's copy or
    a stored version leaves the step less room than an empty device."""
    a, b = _Device(1000, 700), _Device(1000, 300)
    args = ({"p": _leaf((a, 200), (b, 200)), "m": _leaf((a, 100), (b, 100))},
            np.zeros(4))       # a host batch takes no device memory
    # a holds 400 besides the args, b none: a's room decides
    assert memory_limit(args) == 1000 - 700 + 300
    # an empty device: the whole limit, the args' own bytes counted in it
    assert memory_limit(({"p": _leaf((_Device(1000, 200), 200))},)) == 1000
    # a device with no limit to read (the CPU), or no device at all
    assert memory_limit(_args(Backbone(TINY))) is None
    assert memory_limit((np.zeros(4),)) is None


def test_memory_held_besides_the_args_makes_the_step_fall_back():
    """The same step and the same limit: with the device empty but for the
    step's arguments the saving policy fits; with more in use it does not,
    and full remat is compiled instead."""
    bb = Backbone(TINY, compute_dtype=jnp.float32)
    build, args = _jit_builder(bb), _args(bb)
    peak = build("dots").lower(*args).compile().memory_analysis() \
        .peak_memory_in_bytes
    own = sum(a.nbytes for a in jax.tree_util.tree_leaves(args))
    policies = tuple(REMAT_POLICIES)
    for in_use, kept in ((own, "dots"), (own + 64, "full")):
        device = _Device(peak + 32, in_use)
        held = jax.tree_util.tree_map(lambda a: _leaf((device, a.nbytes)),
                                      args)
        _, policy = compile_train_step(build, policies, args,
                                       memory_limit(held))
        assert policy == kept


class _Refused:
    """A jitted step whose compile fails with ``message``."""

    def __init__(self, message):
        self.message = message

    def lower(self, *args):
        def compile_():
            raise jax.errors.JaxRuntimeError(self.message)
        return SimpleNamespace(compile=compile_)


def test_a_compiler_refusal_for_memory_falls_back_to_full_remat():
    bb = Backbone(TINY, compute_dtype=jnp.float32)
    jit = _jit_builder(bb)
    refused = "RESOURCE_EXHAUSTED: Used 15.80G of 15.75G hbm"
    build = lambda p: _Refused(refused) if p == "dots" else jit(p)
    compiled, policy = compile_train_step(build, ("dots", "full"),
                                          _args(bb), None)
    assert policy == "full"
    assert compiled.memory_analysis().temp_size_in_bytes \
        == _temp_bytes(jit, _args(bb), "full")
    # any other failure is the program's, and is raised
    build = lambda p: _Refused("INTERNAL: a compiler fault")
    with pytest.raises(jax.errors.JaxRuntimeError, match="INTERNAL"):
        compile_train_step(build, ("dots", "full"), _args(bb), None)


def _run_trainer(tmp_path, bb, settings=SETTINGS):
    tr = Trainer(bb, adamw.AdamWConfig(lr=1e-3, total_steps=3),
                 DataConfig(vocab=TINY.vocab, seq_len=S, global_batch=B),
                 TrainerConfig(total_steps=3, ckpt_every=100,
                               ckpt_dir=str(tmp_path), log_every=1000),
                 settings)
    try:
        tr.run(tr.init_or_restore())
    finally:
        tr.shutdown()
    return tr


@pytest.mark.parametrize("limit,kept", [(None, "dots"), (1, "full")],
                         ids=["fits", "over-the-limit"])
def test_the_trainer_records_the_policy_its_step_compiled_with(
        tmp_path, monkeypatch, limit, kept):
    """The counters on the trainer site name the policy and the compiled
    step's temporary bytes; the step is compiled once where the saving
    policy fits (the executable that was checked is the one that runs),
    twice where not."""
    monkeypatch.setattr(train_loop, "memory_limit", lambda args: limit)
    reg = metrics.registry(profiling.TRAINER.site)
    reg.reset()
    compiles.LOG.reset()
    tr = _run_trainer(tmp_path, Backbone(TINY, compute_dtype=jnp.float32))
    assert tr.remat_policy == kept
    counters = reg.snapshot()["counters"]
    assert counters[f"train_step.remat.{kept}"] == 1
    temp = tr._compiled.memory_analysis().temp_size_in_bytes
    assert counters["train_step.temp_bytes"] == temp > 0
    built = [r for r in compiles.LOG.records
             if "train_step" in r[0] and r[1] == "compile"]
    assert len(built) == (1 if kept == "dots" else 2)
    assert all(np.isfinite(m["loss"]) for m in tr.metrics_log)
    reg.reset()
    compiles.LOG.reset()


def test_an_explicit_policy_is_honoured(tmp_path, monkeypatch):
    """A policy the settings name is the one compiled, fit or not (the dry
    run's ``--remat-policy``); a backbone without remat checkpoints
    nothing; a name that is no policy is refused."""
    monkeypatch.setattr(train_loop, "memory_limit", lambda args: None)
    bb = Backbone(TINY, compute_dtype=jnp.float32)
    full = StepSettings(zero3=False, gather_weights=False,
                        remat_policy="full")
    assert _run_trainer(tmp_path / "a", bb, full).remat_policy == "full"
    no_remat = Backbone(TINY, compute_dtype=jnp.float32, remat=False)
    assert _run_trainer(tmp_path / "b", no_remat).remat_policy is None
    with pytest.raises(ValueError, match="remat policy"):
        make_train_step(bb, adamw.AdamWConfig(), StepSettings(
            remat_policy="everything"))

    # a step built outside the trainer with no policy named is fully
    # rematerialised, as the explicit "full" step is
    def flops(settings):
        jaxpr = jax.make_jaxpr(make_train_step(bb, adamw.AdamWConfig(),
                                               settings))(*_args(bb))
        return _matmul_flops(jaxpr.jaxpr)

    assert flops(SETTINGS) == flops(full) > flops(
        StepSettings(zero3=False, gather_weights=False, remat_policy="dots"))
