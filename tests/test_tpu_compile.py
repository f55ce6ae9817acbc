"""Compile the chip's main path for a described TPU v5e, with no chip.

The TPU compiler refuses here what interpret mode accepts: misaligned
blocks, too much VMEM, a program that does not fit HBM. Each test lowers
and compiles at real widths for one chip of a ``v5e:2x2`` topology, and
runs nothing. The topology is described inside a fixture, never at import:
only one process at a time may load the TPU library.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_pallas
from repro.launch.hlocost import HloCostModel
from repro.kernels.rglru_kernel import rglru_scan_pallas
from repro.kernels.rwkv6_kernel import rwkv6_scan_pallas
from repro.launch.mesh import dp_axes, make_mesh
from repro.launch.shardings import sharded_backbone, train_state_shardings
from repro.models import Backbone, get_config
from repro.models.backbone import REMAT_POLICIES
from repro.models.config import LayerGroup
from repro.optim import adamw
from repro.runtime.steps import (StepSettings, compile_train_step,
                                 make_train_step, train_state_specs)

HBM_BYTES = 16 * 2 ** 30
# what a v5e's runtime gives a program: "Used 15.80G of 15.75G hbm"
V5E_BYTES_LIMIT = int(15.75 * 2 ** 30)


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


def _activation_matmuls(hlo: HloCostModel, comp: str):
    """Output shapes of the matmuls under ``comp`` (its fusions too)."""
    out = []
    for op in hlo.comps[comp].ops:
        if op.kind in ("dot", "convolution"):
            out.append(tuple(op.out_dims))
        for callee in re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)",
                                 op.rest):
            if callee in hlo.comps:
                out += _activation_matmuls(hlo, callee)
    return out


def _scans(hlo: HloCostModel):
    """(while op, its body's matmul output shapes) of the entry's loops."""
    out = []
    for op in hlo.comps[hlo.entry].ops:
        if op.kind == "while":
            body = re.search(r"body=%?([\w.\-]+)", op.rest).group(1)
            out.append((op, _activation_matmuls(hlo, body)))
    return out


def test_cell_train_step_keeps_the_projections_on_one_v5e(one_chip):
    """The benchmark cells' step (qwen3-4b, 5 layers, batch 4 x 512, fp32
    params and AdamW state) keeps the saving policy with room under 15 GiB,
    and its backward layer scan recomputes no projection: it takes each
    layer's bf16 q, k, v, o, gate and up from the forward, and holds one
    [batch, seq, ...] matmul per projection (that projection's input
    gradient), as many as the forward scan, where full remat would hold
    six more."""
    B, S, layers, limit = 4, 512, 5, int(15.0 * 2 ** 30)
    cfg = _qwen3_4b(layers)
    settings = StepSettings()
    bb = Backbone(cfg, remat=settings.remat)
    state = _on_chip(one_chip, train_state_specs(bb, settings))
    batch = {k: _spec(one_chip, (B, S), jnp.int32)
             for k in ("tokens", "labels")}

    def build(policy):
        s = dataclasses.replace(settings, remat_policy=policy)
        return jax.jit(make_train_step(bb, adamw.AdamWConfig(), s),
                       donate_argnums=(0,))

    compiled, policy = compile_train_step(build, tuple(REMAT_POLICIES),
                                          (state, batch), limit)
    assert policy == "dots"
    assert compiled.memory_analysis().peak_memory_in_bytes <= limit

    hlo = HloCostModel(compiled.as_text())
    scans = sorted(([op, [d for d in mm if list(d[:2]) == [B, S]]]
                    for op, mm in _scans(hlo)), key=lambda x: len(x[1]))
    (_, forward), (loop, backward) = scans[-2:]
    assert len(forward) == 7, forward
    assert len(backward) == len(forward), backward
    # a [B, S, k or v width] matmul is only ever the forward's projection
    kv = cfg.n_kv_heads * cfg.hd
    assert not [d for d in backward if d[2:] in ((kv,), (cfg.n_kv_heads,
                                                          cfg.hd))]
    # the residuals it reads instead, stacked over the layers
    for width in (cfg.n_heads * cfg.hd, kv, cfg.d_model, cfg.d_ff):
        assert f"bf16[{layers},{B},{S},{width}]" in loop.out_text, width


def test_sharded_train_step_policy_on_a_v5e_2x2(topo):
    """The four-chip smoke's sharded trainer (qwen3-4b at 8 layers, batch
    4 x 512, ZeRO-3 over a 2x2 (data, model) mesh), jitted as ``Trainer``
    jits it on a mesh: the saving policy fits each chip's share, so it is
    the one kept there too."""
    from chip_smoke import MESH_LAYERS, TRAIN_BATCH, TRAIN_SEQ
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    settings = StepSettings()
    bb, p_sh = sharded_backbone(_qwen3_4b(MESH_LAYERS), mesh, TRAIN_BATCH,
                                settings)
    st_sh = train_state_shardings(p_sh, mesh, settings)
    batch_sh = NamedSharding(mesh, PartitionSpec(dp_axes(mesh) or None))
    state = jax.tree_util.tree_map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        train_state_specs(bb, settings), st_sh)
    batch = {k: jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32,
                                     sharding=batch_sh)
             for k in ("tokens", "labels")}

    def build(policy):
        s = dataclasses.replace(settings, remat_policy=policy)
        return jax.jit(make_train_step(bb, adamw.AdamWConfig(), s),
                       in_shardings=(st_sh, batch_sh),
                       out_shardings=(st_sh, NamedSharding(mesh,
                                                           PartitionSpec())),
                       donate_argnums=(0,))

    compiled, policy = compile_train_step(build, tuple(REMAT_POLICIES),
                                          (state, batch), V5E_BYTES_LIMIT)
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert policy == "dots"
    assert peak <= V5E_BYTES_LIMIT
