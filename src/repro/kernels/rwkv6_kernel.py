"""RWKV-6 WKV recurrence Pallas kernel (TPU target).

The WKV state ``S ∈ R^{hd×hd}`` per (batch, head) stays resident in VMEM
scratch across time chunks: grid ``(B·H, nt)`` with the time dimension
innermost/sequential. Each grid step streams one ``[block_t, hd]`` tile of
r/k/v/w from HBM into VMEM and walks it with a ``fori_loop`` of rank-1
updates (VPU work — the recurrence is elementwise/outer-product shaped, so
the MXU has nothing to chew on; a chunked matmul reformulation would give
it some). The tiles are fp32 and ``u`` rides as ``[B·H, 1, hd]``, so every
block's last two dims are tile-aligned or whole, as the TPU compiler
requires.

The initial state is read once at ``ti == 0`` and the final state written
at ``ti == nt-1``, so checkpointed decode (long_500k) round-trips state
exactly.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ref as kref


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
                state_s, *, block_t: int, nt: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _load_state():
        state_s[...] = s0_ref[0].astype(jnp.float32)

    u = u_ref[0, 0].astype(jnp.float32)                 # [hd]

    def step(t, _):
        rt = r_ref[0, t, :].astype(jnp.float32)         # [hd]
        kt = k_ref[0, t, :].astype(jnp.float32)
        vt = v_ref[0, t, :].astype(jnp.float32)
        wt = w_ref[0, t, :].astype(jnp.float32)
        s = state_s[...]                                # [hd, hd] (k-major)
        kv = kt[:, None] * vt[None, :]                  # outer product
        y = jnp.sum((s + u[:, None] * kv) * rt[:, None], axis=0)
        y_ref[0, t, :] = y.astype(y_ref.dtype)
        state_s[...] = wt[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, block_t, step, 0)

    @pl.when(ti == nt - 1)
    def _store_state():
        sT_ref[0] = state_s[...]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def rwkv6_scan_pallas(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                      u: jax.Array, state: jax.Array, block_t: int = 64,
                      interpret: bool = False
                      ) -> Tuple[jax.Array, jax.Array]:
    """r,k,v,w: [B,T,H,hd]; u: [H,hd]; state: [B,H,hd,hd] -> (y fp32, state fp32).

    Differentiable: the backward pass is the VJP of ``ref.rwkv6_scan_ref``.
    """
    return _scan(r, k, v, w, u, state, block_t, interpret)


def _scan_fwd(r, k, v, w, u, state, block_t, interpret):
    return (_scan(r, k, v, w, u, state, block_t, interpret),
            (r, k, v, w, u, state))


def _scan_bwd(block_t, interpret, res, g):
    return jax.vjp(kref.rwkv6_scan_ref, *res)[1](g)


rwkv6_scan_pallas.defvjp(_scan_fwd, _scan_bwd)


def _scan(r, k, v, w, u, state, block_t, interpret):
    B, T, H, hd = r.shape
    block_t = min(block_t, T)
    pad_t = (-T) % block_t
    # fp32 tiles: the kernel reads one row per step at a dynamic offset,
    # which Mosaic cannot prove aligned for packed (bf16) rows
    fold = lambda a: a.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        B * H, T, hd)
    rf, kf, vf, wf = map(fold, (r, k, v, w))
    if pad_t:
        # pad with w=1, k=0: state is untouched by padded steps
        zpad = ((0, 0), (0, pad_t), (0, 0))
        rf, kf, vf = (jnp.pad(a, zpad) for a in (rf, kf, vf))
        wf = jnp.pad(wf, zpad, constant_values=1.0)
    Tp = T + pad_t
    nt = Tp // block_t
    # [B*H, 1, hd]: a block's last two dims then equal the array's
    uf = jnp.tile(u, (B, 1))[:, None, :]
    sf = state.reshape(B * H, hd, hd)

    kernel = functools.partial(_wkv_kernel, block_t=block_t, nt=nt)
    seq_map = lambda bh, ti: (bh, ti, 0)
    st_map = lambda bh, ti: (bh, 0, 0)

    y, sT = pl.pallas_call(
        kernel,
        grid=(B * H, nt),
        in_specs=[
            pl.BlockSpec((1, block_t, hd), seq_map),    # r
            pl.BlockSpec((1, block_t, hd), seq_map),    # k
            pl.BlockSpec((1, block_t, hd), seq_map),    # v
            pl.BlockSpec((1, block_t, hd), seq_map),    # w
            pl.BlockSpec((1, 1, hd), st_map),           # u
            pl.BlockSpec((1, hd, hd), st_map),          # s0
        ],
        out_specs=[
            pl.BlockSpec((1, block_t, hd), seq_map),    # y
            pl.BlockSpec((1, hd, hd), st_map),          # final state
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tp, hd), jnp.float32),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(rf, kf, vf, wf, uf, sf)

    y = y[:, :T].reshape(B, H, T, hd).transpose(0, 2, 1, 3)
    return y, sT.reshape(B, H, hd, hd)
