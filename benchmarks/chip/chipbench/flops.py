"""Operations and bytes the work needs, computed from shapes alone.

These are the yardstick: what the algorithm needs, not what the compiled
program happens to do. Recomputation (remat's second forward) is not
counted, and causal attention counts the half of the score matrix it uses.
Configurations are the benchmark's JSON files, keyed as their sources are.
Each model type's own counts are in ``archs/<model_type>.py``; the
functions here hand a configuration to its model type's file.
"""
from __future__ import annotations

from typing import Dict, Iterable

from chipbench import harness


def _arch(cfg: Dict):
    return harness.arch(cfg["model_type"])


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one layer multiplies every token by (matmul operands only)."""
    return _arch(cfg).layer_matmul_params(cfg)


def matmul_params(cfg: Dict) -> int:
    """All layers' matmul weights plus the output head (D x vocab). A model
    type whose layers differ in their weights gives its own count."""
    own = getattr(_arch(cfg), "matmul_params", None)
    if own is not None:
        return own(cfg)
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def param_count(cfg: Dict) -> int:
    """Every parameter of the configuration (a tied embedding once)."""
    return _arch(cfg).param_count(cfg)


def causal_pairs(q_len: int, past: int = 0) -> int:
    """(query, key) pairs a causal pass of ``q_len`` tokens after ``past``
    cached ones attends to."""
    return q_len * past + q_len * (q_len + 1) // 2


def attn_flops(cfg: Dict, pairs: int) -> float:
    """Forward score and context products over ``pairs`` (q, k) pairs."""
    return _arch(cfg).attn_flops(cfg, pairs)


def wkv_fwd_flops(B: int, T: int, H: int, hd: int) -> float:
    """WKV forward per (batch, step, head): the k v^T outer product, the
    bonus term, the contraction with r and the decayed state update."""
    return 7.0 * B * T * H * hd * hd


def wkv_fwd_bytes(B: int, T: int, H: int, hd: int, rkv_bytes: int,
                  w_bytes: int) -> float:
    """r, k, v (``rkv_bytes`` each), w and u in; y and the state out; the
    initial state in. Output y and both states are float32."""
    seq = B * T * H * hd
    return (seq * (3 * rkv_bytes + w_bytes + 4) + H * hd * 4
            + 2 * B * H * hd * hd * 4)


def train_step_flops(cfg: Dict, batch: int, seq: int) -> float:
    """Forward and backward of one step: 6 x matmul weights x tokens, plus
    three times the forward products of the token mixer (attention, WKV)."""
    return _arch(cfg).train_step_flops(cfg, batch, seq)


def serve_flops(cfg: Dict, tokens: int, pairs: int) -> float:
    """Forward over ``tokens`` tokens that attend to ``pairs`` pairs."""
    return 2.0 * matmul_params(cfg) * tokens + attn_flops(cfg, pairs)


def decode_step_bytes(cfg: Dict, contexts: Iterable[int],
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """HBM bytes one decode step needs: every weight once, and the cache of
    the live positions of the live slots only."""
    per_pos = _arch(cfg).cache_bytes_per_position(cfg, kv_bytes)
    return param_count(cfg) * weight_bytes + per_pos * sum(contexts)
