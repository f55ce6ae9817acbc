"""Compile the chip's main path for a described TPU v5e, with no chip.

The TPU compiler refuses here what interpret mode accepts: misaligned
blocks, too much VMEM, a program that does not fit HBM. Each test lowers
and compiles at real widths for one chip of a ``v5e:2x2`` topology, and
runs nothing. The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rglru_kernel import rglru_scan_pallas
from repro.kernels.rwkv6_kernel import rwkv6_scan_pallas
from repro.models import Backbone, get_config
from repro.models.config import LayerGroup
from repro.optim import adamw
from repro.runtime.steps import (StepSettings, make_train_step,
                                 train_state_specs)

HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_case(name, one_chip):
    """(fn, arg specs) at the widths of rwkv6-3b, recurrentgemma-9b and
    qwen3-4b's attention."""
    s = lambda shape, dt=jnp.float32: _spec(one_chip, shape, dt)
    if name == "rwkv6":                        # H=40 heads of 64
        B, T, H, hd = 2, 256, 40, 64
        fn = lambda r, k, v, w, u, s0: rwkv6_scan_pallas(r, k, v, w, u, s0,
                                                         64, False)
        bf = lambda: s((B, T, H, hd), jnp.bfloat16)
        return fn, (bf(), bf(), bf(), s((B, T, H, hd)), s((H, hd)),
                    s((B, H, hd, hd)))
    if name == "rglru":                        # width 4096
        B, T, W = 2, 256, 4096
        fn = lambda x, a, r, i, h: rglru_scan_pallas(x, a, r, i, h, 128, 512,
                                                     False)
        bf = lambda: s((B, T, W), jnp.bfloat16)
        return fn, (bf(), s((W,)), bf(), bf(), s((B, W)))
    B, S, Hq, Hkv, hd = 1, 1024, 32, 8, 128    # GQA 32/8, head_dim 128
    fn = lambda q, k, v: flash_attention_pallas(q, k, v)
    return fn, (s((B, S, Hq, hd), jnp.bfloat16),
                s((B, S, Hkv, hd), jnp.bfloat16),
                s((B, S, Hkv, hd), jnp.bfloat16))


@pytest.mark.parametrize("name", ["rwkv6", "rglru", "flash"])
def test_kernel_forward_compiles_for_v5e(name, one_chip):
    fn, args = _kernel_case(name, one_chip)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("name", ["rwkv6", "rglru"])
def test_kernel_grad_compiles_for_v5e(name, one_chip):
    fn, args = _kernel_case(name, one_chip)

    def loss(*a):
        return sum(jnp.sum(o) for o in fn(*a))

    # value_and_grad: the backward is the oracle's VJP, so a bare grad
    # would let XLA drop the kernel's unused forward
    grad = jax.value_and_grad(loss, argnums=tuple(range(len(args))))
    hlo = jax.jit(grad).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _qwen3_4b(layers):
    """qwen3-4b at its full widths, depth cut to ``layers``."""
    return dataclasses.replace(get_config("qwen3-4b"),
                               groups=(LayerGroup(("attn",), layers),))


def _on_chip(one_chip, tree):
    return jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype), tree)


def test_serve_prefill_and_decode_compile_for_v5e(one_chip):
    """The server's prefill and decode steps at qwen3-4b's widths (depth
    cut to 2: the programs are the same scan at any depth)."""
    bb = Backbone(_qwen3_4b(2), param_dtype=jnp.bfloat16, remat=False)
    params = _on_chip(one_chip, jax.eval_shape(bb.init, jax.random.PRNGKey(0)))
    slots, prompt, ctx = 4, 64, 80
    prefill = jax.jit(lambda p, b: bb.prefill(p, b, ctx)).lower(
        params, {"tokens": _spec(one_chip, (1, prompt), jnp.int32)}).compile()
    cache = _on_chip(one_chip, jax.eval_shape(lambda: bb.init_cache(slots, ctx)))
    decode = jax.jit(bb.decode_step).lower(
        params, cache, _spec(one_chip, (slots, 1), jnp.int32)).compile()
    for c in (prefill, decode):
        assert c.memory_analysis().peak_memory_in_bytes < HBM_BYTES


def test_smoke_train_step_fits_one_v5e(one_chip):
    """The chip smoke's qwen3-4b train step, at full widths and its cut
    depth, fits one chip's HBM with fp32 params, m, v and grads."""
    from chip_smoke import ARCH, TRAIN_BATCH, TRAIN_LAYERS, TRAIN_SEQ
    assert ARCH == "qwen3-4b"

    settings = StepSettings()
    bb = Backbone(_qwen3_4b(TRAIN_LAYERS), remat=settings.remat)
    state = _on_chip(one_chip, train_state_specs(bb, settings))
    batch = {k: _spec(one_chip, (TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(bb, adamw.AdamWConfig(), settings),
                   donate_argnums=(0,))
    mem = step.lower(state, batch).compile().memory_analysis()
    assert mem.peak_memory_in_bytes < HBM_BYTES
    # the donated state is reused in place, so the peak is more than it
    assert mem.peak_memory_in_bytes > mem.argument_size_in_bytes
