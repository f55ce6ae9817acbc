"""RG-LRU Pallas kernel (TPU target).

Grid ``(B, nw, nt)``: the hidden width is tiled into lane-aligned blocks of
``block_w`` channels (the recurrence is channel-diagonal, so width blocks
are independent and parallel); time is innermost/sequential with the
per-(batch, width-block) state held in VMEM scratch. Each step is pure VPU
elementwise work on one ``[1, block_w]`` row. Every block is 2-D with its
last two dims tile-aligned or whole (``a_log`` rides as ``[1, W]``, ``h0``
and ``hT`` as ``[B, 1, W]``), which the TPU compiler requires.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as kref

RGLRU_C = 8.0


def _rglru_kernel(x_ref, alog_ref, r_ref, i_ref, h0_ref, y_ref, hT_ref,
                  h_s, *, block_t: int, nt: int):
    ti = pl.program_id(2)

    @pl.when(ti == 0)
    def _load():
        h_s[...] = h0_ref[0].astype(jnp.float32)

    decay = jax.nn.softplus(alog_ref[...].astype(jnp.float32))  # [1, block_w]

    def step(t, _):
        row = pl.ds(t, 1)
        xt = x_ref[0, row, :].astype(jnp.float32)               # [1, block_w]
        rt = r_ref[0, row, :].astype(jnp.float32)
        it = i_ref[0, row, :].astype(jnp.float32)
        a = jnp.exp(-RGLRU_C * decay * rt)
        h = a * h_s[...] + jnp.sqrt(jnp.maximum(1.0 - a * a, 0.0)) * (it * xt)
        h_s[...] = h
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return 0

    jax.lax.fori_loop(0, block_t, step, 0)

    @pl.when(ti == nt - 1)
    def _store():
        hT_ref[0] = h_s[...]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def rglru_scan_pallas(x: jax.Array, a_log: jax.Array, gate_r: jax.Array,
                      gate_i: jax.Array, h0: jax.Array, block_t: int = 128,
                      block_w: int = 512, interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """x, gate_r, gate_i: [B,T,W]; a_log: [W]; h0: [B,W] -> (y fp32 [B,T,W], hT).

    Differentiable: the backward pass is the VJP of ``ref.rglru_scan_ref``.
    """
    return _scan(x, a_log, gate_r, gate_i, h0, block_t, block_w, interpret)


def _scan_fwd(x, a_log, gate_r, gate_i, h0, block_t, block_w, interpret):
    return (_scan(x, a_log, gate_r, gate_i, h0, block_t, block_w, interpret),
            (x, a_log, gate_r, gate_i, h0))


def _scan_bwd(block_t, block_w, interpret, res, g):
    return jax.vjp(kref.rglru_scan_ref, *res)[1](g)


rglru_scan_pallas.defvjp(_scan_fwd, _scan_bwd)


def _scan(x, a_log, gate_r, gate_i, h0, block_t, block_w, interpret):
    B, T, W = x.shape
    block_t = min(block_t, T)
    block_w = min(block_w, W)
    pad_t = (-T) % block_t
    pad_w = (-W) % block_w
    # fp32 tiles: the kernel reads one row per step at a dynamic offset,
    # which Mosaic cannot prove aligned for packed (bf16) rows
    x, gate_r, gate_i = (a.astype(jnp.float32) for a in (x, gate_r, gate_i))
    if pad_t or pad_w:
        pt = ((0, 0), (0, pad_t), (0, pad_w))
        x = jnp.pad(x, pt)
        gate_r = jnp.pad(gate_r, pt)
        gate_i = jnp.pad(gate_i, pt)
        a_log = jnp.pad(a_log, (0, pad_w))
        h0 = jnp.pad(h0, ((0, 0), (0, pad_w)))
    Tp, Wp = T + pad_t, W + pad_w
    nt, nw = Tp // block_t, Wp // block_w

    kernel = functools.partial(_rglru_kernel, block_t=block_t, nt=nt)
    seq_map = lambda b, wi, ti: (b, ti, wi)
    w_map = lambda b, wi, ti: (0, wi)
    h_map = lambda b, wi, ti: (b, 0, wi)

    y, hT = pl.pallas_call(
        kernel,
        grid=(B, nw, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, block_w), seq_map),   # x
            pl.BlockSpec((1, block_w), w_map),              # a_log
            pl.BlockSpec((1, block_t, block_w), seq_map),   # gate_r
            pl.BlockSpec((1, block_t, block_w), seq_map),   # gate_i
            pl.BlockSpec((1, 1, block_w), h_map),           # h0
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, block_w), seq_map),   # y
            pl.BlockSpec((1, 1, block_w), h_map),           # hT
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Tp, Wp), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, Wp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
    )(x, a_log[None, :], gate_r, gate_i, h0[:, None, :])
    return y[:, :T, :W], hT[:, 0, :W]
