"""Jitted dispatch wrappers for the Pallas kernels.

Selection policy (``impl`` argument, default ``"auto"``):

* ``"auto"``    — the compiled Pallas kernel on TPU backends; the pure-jnp
  reference path elsewhere.
* ``"pallas"``  — force the kernel; off-TPU it runs in interpret mode (how
  the CPU tests exercise it). On a TPU it is always compiled.
* ``"ref"``     — force the jnp oracle.

The models only ever import these wrappers, so swapping the execution
substrate never touches model code.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref as kref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    if impl == "auto":
        return "pallas" if _on_tpu() else "ref"
    return impl


# --------------------------------------------------------------------------- #
# Flash attention                                                              #
# --------------------------------------------------------------------------- #
def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    logit_cap: Optional[float] = None,
                    q_offset: int = 0, impl: str = "auto",
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    impl = _resolve(impl)
    if impl == "pallas":
        from .flash_attention import flash_attention_pallas
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, logit_cap=logit_cap,
            q_offset=q_offset, block_q=block_q, block_k=block_k,
            interpret=not _on_tpu())
    # jnp fallback: the chunked online-softmax implementation from models
    from repro.models.attention import flash_attention_jnp
    q_positions = q_offset + jnp.arange(q.shape[1], dtype=jnp.int32)
    return flash_attention_jnp(q, k, v, causal=causal, window=window,
                               logit_cap=logit_cap, q_positions=q_positions)


# --------------------------------------------------------------------------- #
# RWKV-6 WKV scan                                                              #
# --------------------------------------------------------------------------- #
def rwkv6_scan(r, k, v, w, u, state, *, impl: str = "auto",
               block_t: int = 64) -> Tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    if impl == "pallas":
        from .rwkv6_kernel import rwkv6_scan_pallas
        return rwkv6_scan_pallas(r, k, v, w, u, state, block_t,
                                 not _on_tpu())
    return kref.rwkv6_scan_ref(r, k, v, w, u, state)


# --------------------------------------------------------------------------- #
# RG-LRU scan                                                                  #
# --------------------------------------------------------------------------- #
def rglru_scan(x, a_log, gate_r, gate_i, h0, *, impl: str = "auto",
               block_t: int = 128, block_w: int = 512
               ) -> Tuple[jax.Array, jax.Array]:
    impl = _resolve(impl)
    if impl == "pallas":
        from .rglru_kernel import rglru_scan_pallas
        return rglru_scan_pallas(x, a_log, gate_r, gate_i, h0, block_t,
                                 block_w, not _on_tpu())
    return kref.rglru_scan_ref(x, a_log, gate_r, gate_i, h0)
