"""Attention: GQA with RoPE, windows, soft-capping; flash-style jnp fallback.

Every model path — training, prefill and decode, on every backend — runs
``flash_attention_jnp``: a two-level-chunked online-softmax attention
expressed with ``lax.scan`` so that it lowers anywhere with O(chunk) memory,
with a flash-style custom VJP. The Pallas forward kernel in
``repro.kernels.flash_attention`` implements the same algorithm; it is
reached only through ``repro.kernels.ops.flash_attention`` (tests and the
chip smoke check it against ``kernels.ref``), not from the models.

GQA is computed in grouped form (queries reshaped to [B,S,n_kv,G,hd]) so KV
heads are never materialized repeated.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import apply_rope, softcap

MASK_VALUE = -1e30


def _chunk_attn_block(q, k, v, q_pos, kv_pos, *, causal: bool,
                      window: Optional[int], logit_cap: Optional[float],
                      carry=None):
    """One (q-chunk × kv-chunk) online-softmax block.

    q: [B, Cq, Hkv, G, hd]; k/v: [B, Ck, Hkv, hd];
    q_pos: [Cq]; kv_pos: [Ck]. carry = (m, l, acc) running stats.
    Returns the updated carry.
    """
    B, Cq, Hkv, G, hd = q.shape
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if logit_cap is not None:
        s = softcap(s, logit_cap)
    dpos = q_pos[:, None] - kv_pos[None, :]  # [Cq, Ck]
    valid = kv_pos[None, :] >= 0
    if causal:
        valid &= dpos >= 0
    if window is not None:
        valid &= dpos < window
    s = jnp.where(valid[None, None, None, :, :], s, MASK_VALUE)
    m_new = jnp.maximum(carry[0], jnp.max(s, axis=-1))        # [B,Hkv,G,Cq]
    p = jnp.exp(s - m_new[..., None])
    alpha = jnp.exp(carry[0] - m_new)
    l_new = carry[1] * alpha + jnp.sum(p, axis=-1)
    acc = carry[2] * alpha[..., None] + jnp.einsum(
        "bhgqk,bkhd->bhgqd", p, v.astype(jnp.float32))
    return (m_new, l_new, acc)


def flash_attention_jnp(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        logit_cap: Optional[float] = None,
                        q_positions: Optional[jax.Array] = None,
                        kv_positions: Optional[jax.Array] = None,
                        q_chunk: int = 512,
                        kv_chunk: int = 1024) -> jax.Array:
    """Online-softmax chunked attention with a flash custom VJP.

    q: [B, Sq, Hq, hd]; k, v: [B, Skv, Hkv, hd]. Positions default to
    aligned causal layouts; pass explicit positions for decode/ring caches
    (kv position ``-1`` marks an empty slot).
    Returns [B, Sq, Hq, hd] in q.dtype.

    The backward pass recomputes score blocks chunk-by-chunk (the flash
    backward algorithm) instead of letting autodiff stack per-chunk
    residuals across the scan.
    """
    if q_positions is None:
        q_positions = jnp.arange(q.shape[1], dtype=jnp.int32)
    if kv_positions is None:
        kv_positions = jnp.arange(k.shape[1], dtype=jnp.int32)
    return _flash(q, k, v, q_positions, kv_positions, causal, window,
                  logit_cap, q_chunk, kv_chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash(q, k, v, q_positions, kv_positions, causal, window, logit_cap,
           q_chunk, kv_chunk):
    out, _ = _flash_fwd_impl(q, k, v, q_positions, kv_positions, causal,
                             window, logit_cap, q_chunk, kv_chunk)
    return out


def _flash_vjp_fwd(q, k, v, q_positions, kv_positions, causal, window,
                   logit_cap, q_chunk, kv_chunk):
    out, lse = _flash_fwd_impl(q, k, v, q_positions, kv_positions, causal,
                               window, logit_cap, q_chunk, kv_chunk)
    return out, (q, k, v, q_positions, kv_positions, out, lse)


def _flash_vjp_bwd(causal, window, logit_cap, q_chunk, kv_chunk, res, dout):
    q, k, v, q_positions, kv_positions, out, lse = res
    dq, dk, dv = _flash_bwd_impl(q, k, v, q_positions, kv_positions, out,
                                 lse, dout, causal, window, logit_cap,
                                 q_chunk, kv_chunk)
    f0 = lambda a: jnp.zeros(a.shape, jax.dtypes.float0)
    return dq, dk, dv, f0(q_positions), f0(kv_positions)


def _flash_fwd_impl(q, k, v, q_positions, kv_positions, causal, window,
                    logit_cap, q_chunk, kv_chunk):
    """Returns (out [B,Sq,Hq,hd], lse [B,Hkv,G,Sq] fp32)."""
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    # pad to multiples
    nq = -(-Sq // q_chunk)
    nk = -(-Skv // kv_chunk)
    pad_q = nq * q_chunk - Sq
    pad_k = nk * kv_chunk - Skv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, (0, pad_q), constant_values=0)
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        kv_positions = jnp.pad(kv_positions, (0, pad_k), constant_values=-1)

    qg = q.reshape(B, nq, q_chunk, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    qp = q_positions.reshape(nq, q_chunk)
    kg = k.reshape(B, nk, kv_chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vg = v.reshape(B, nk, kv_chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    kp = kv_positions.reshape(nk, kv_chunk)

    def q_body(_, q_in):
        qc, qpos = q_in

        def kv_body(carry, kv_in):
            kc, vc, kpos = kv_in
            return _chunk_attn_block(qc, kc, vc, qpos, kpos, causal=causal,
                                     window=window, logit_cap=logit_cap,
                                     carry=carry), None

        m0 = jnp.full((B, Hkv, G, q_chunk), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, q_chunk), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, q_chunk, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_body, (m0, l0, a0), (kg, vg, kp))
        out = acc / jnp.maximum(l, 1e-30)[..., None]          # [B,Hkv,G,Cq,hd]
        lse = m + jnp.log(jnp.maximum(l, 1e-30))              # [B,Hkv,G,Cq]
        return None, (out.transpose(0, 3, 1, 2, 4), lse)

    # vmem_kernel scope: the HLO cost model (hlocost) charges this loop nest
    # bytes for its I/O only, as if it were one fused kernel like
    # repro.kernels.flash_attention; XLA runs it as the scan it is.
    with jax.named_scope("vmem_kernel_flash"):
        _, (outs, lses) = jax.lax.scan(q_body, None, (qg, qp))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * q_chunk, Hq, hd)
    lse = lses.transpose(1, 2, 3, 0, 4).reshape(B, Hkv, G, nq * q_chunk)
    return out[:, :Sq].astype(q.dtype), lse[..., :Sq]


def _flash_bwd_impl(q, k, v, q_positions, kv_positions, out, lse, dout,
                    causal, window, logit_cap, q_chunk, kv_chunk):
    """Flash backward: per-block score recomputation, no stacked residuals.

    Outer scan over kv chunks carrying the dq accumulator; inner scan over
    q chunks emitting (dk, dv) per kv chunk. All fp32 accumulation.
    """
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = 1.0 / (hd ** 0.5)
    q_chunk = min(q_chunk, Sq)
    kv_chunk = min(kv_chunk, Skv)
    nq = -(-Sq // q_chunk)
    nk = -(-Skv // kv_chunk)
    pad_q = nq * q_chunk - Sq
    pad_k = nk * kv_chunk - Skv

    def padq(a, fill=0):
        return jnp.pad(a, ((0, 0), (0, pad_q)) + ((0, 0),) * (a.ndim - 2),
                       constant_values=fill) if pad_q else a

    qp = jnp.pad(q_positions, (0, pad_q), constant_values=-(10 ** 9)) \
        if pad_q else q_positions
    kp = jnp.pad(kv_positions, (0, pad_k), constant_values=-1) \
        if pad_k else kv_positions
    qf = padq(q)
    outf = padq(out)
    doutf = padq(dout)
    lsef = jnp.pad(lse, ((0, 0),) * 3 + ((0, pad_q),)) if pad_q else lse
    kf = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else k
    vf = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0))) if pad_k else v

    Sqp, Skvp = Sq + pad_q, Skv + pad_k
    # delta_i = rowsum(dout * out)  [B, Hkv, G, Sqp]
    delta = jnp.einsum(
        "bshd,bshd->bhs",
        doutf.astype(jnp.float32), outf.astype(jnp.float32)
    ).reshape(B, Hkv, G, Sqp)

    qg = qf.reshape(B, nq, q_chunk, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    dog = doutf.reshape(B, nq, q_chunk, Hkv, G, hd).transpose(1, 0, 2, 3, 4, 5)
    lseg = lsef.reshape(B, Hkv, G, nq, q_chunk).transpose(3, 0, 1, 2, 4)
    dg = delta.reshape(B, Hkv, G, nq, q_chunk).transpose(3, 0, 1, 2, 4)
    qpg = qp.reshape(nq, q_chunk)
    kg = kf.reshape(B, nk, kv_chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    vg = vf.reshape(B, nk, kv_chunk, Hkv, hd).transpose(1, 0, 2, 3, 4)
    kpg = kp.reshape(nk, kv_chunk)

    def block_grads(qc, doc, lsec, dc, qpos, kc, vc, kpos):
        """One (q-chunk, kv-chunk) block; returns (dq_c, dk_c, dv_c)."""
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qc.astype(jnp.float32),
                       kc.astype(jnp.float32)) * scale
        if logit_cap is not None:
            t = jnp.tanh(s / logit_cap)
            u_grad = 1.0 - jnp.square(t)          # ds/du
            s = logit_cap * t
        dpos = qpos[:, None] - kpos[None, :]
        valid = kpos[None, :] >= 0
        if causal:
            valid &= dpos >= 0
        if window is not None:
            valid &= dpos < window
        p = jnp.where(valid[None, None, None],
                      jnp.exp(s - lsec[..., None]), 0.0)     # [B,h,g,q,k]
        dv_c = jnp.einsum("bhgqk,bqhgd->bkhd", p, doc.astype(jnp.float32))
        dp = jnp.einsum("bqhgd,bkhd->bhgqk", doc.astype(jnp.float32),
                        vc.astype(jnp.float32))
        ds = p * (dp - dc[..., None])
        if logit_cap is not None:
            ds = ds * u_grad
        ds = ds * scale
        dq_c = jnp.einsum("bhgqk,bkhd->bqhgd", ds, kc.astype(jnp.float32))
        dk_c = jnp.einsum("bhgqk,bqhgd->bkhd", ds, qc.astype(jnp.float32))
        return dq_c, dk_c, dv_c

    def kv_body(dq_acc, kv_in):
        kc, vc, kpos = kv_in

        def q_body(carry, q_in):
            dk_a, dv_a = carry
            qc, doc, lsec, dc, qpos = q_in
            dq_c, dk_c, dv_c = block_grads(qc, doc, lsec, dc, qpos,
                                           kc, vc, kpos)
            return (dk_a + dk_c, dv_a + dv_c), dq_c

        dk0 = jnp.zeros((B, kv_chunk, Hkv, hd), jnp.float32)
        (dk_j, dv_j), dq_chunks = jax.lax.scan(
            q_body, (dk0, dk0), (qg, dog, lseg, dg, qpg))
        return dq_acc + dq_chunks, (dk_j, dv_j)

    dq0 = jnp.zeros((nq, B, q_chunk, Hkv, G, hd), jnp.float32)
    with jax.named_scope("vmem_kernel_flash_bwd"):
        dq, (dk, dv) = jax.lax.scan(kv_body, dq0, (kg, vg, kpg))
    dq = dq.transpose(1, 0, 2, 3, 4, 5).reshape(B, Sqp, Hq, hd)[:, :Sq]
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(B, Skvp, Hkv, hd)[:, :Skv]
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(B, Skvp, Hkv, hd)[:, :Skv]
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype))


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def attention_reference(q, k, v, *, causal=True, window=None, logit_cap=None,
                        q_positions=None, kv_positions=None) -> jax.Array:
    """Unchunked oracle for tests (materializes full scores)."""
    B, Sq, Hq, hd = q.shape
    _, Skv, Hkv, _ = k.shape
    G = Hq // Hkv
    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32)
    if kv_positions is None:
        kv_positions = jnp.arange(Skv, dtype=jnp.int32)
    qg = q.reshape(B, Sq, Hkv, G, hd)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    if logit_cap is not None:
        s = softcap(s, logit_cap)
    dpos = q_positions[:, None] - kv_positions[None, :]
    valid = kv_positions[None, :] >= 0
    if causal:
        valid &= dpos >= 0
    if window is not None:
        valid &= dpos < window
    s = jnp.where(valid[None, None, None], s, MASK_VALUE)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, Hq, hd).astype(q.dtype)
