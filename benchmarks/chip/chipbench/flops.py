"""Operations and bytes the work needs, computed from shapes alone.

These are the yardstick: what the algorithm needs, not what the compiled
program happens to do. Recomputation (remat's second forward) is not
counted, and causal attention counts the half of the score matrix it uses.
Configurations are the benchmark's JSON files, keyed as their sources are.
"""
from __future__ import annotations

from typing import Dict, Iterable


def _attn_shape(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"])


def layer_matmul_params(cfg: Dict) -> int:
    """Weights one layer multiplies every token by (matmul operands only)."""
    if cfg["model_type"] == "qwen3":
        D, H, KV, hd, F = _attn_shape(cfg)
        return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    if cfg["model_type"] == "rwkv6":
        D, F = cfg["hidden_size"], cfg["intermediate_size"]
        Dr = cfg["num_attention_heads"] * cfg["head_size"]
        mix, dec = cfg["time_mix_extra_dim"], cfg["time_decay_extra_dim"]
        # r, k, v, g and the output; five data-dependent lerps (each a
        # D->mix->D low-rank pair); the decay's D->dec->Dr pair; channel mix
        return (4 * D * Dr + Dr * D + 5 * (D * mix + mix * D)
                + D * dec + dec * Dr + 2 * D * F + D * D)
    raise KeyError(f"no FLOP count for model_type {cfg['model_type']!r}")


def matmul_params(cfg: Dict) -> int:
    """All layers' matmul weights plus the output head (D x vocab)."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def param_count(cfg: Dict) -> int:
    """Every parameter of a qwen3 configuration (tied embedding once)."""
    D, H, KV, hd, F = _attn_shape(cfg)
    layer = layer_matmul_params(cfg) + 2 * D + 2 * hd
    head = 1 if cfg["tie_word_embeddings"] else 2
    return cfg["num_hidden_layers"] * layer + head * cfg["vocab_size"] * D + D


def causal_pairs(q_len: int, past: int = 0) -> int:
    """(query, key) pairs a causal pass of ``q_len`` tokens after ``past``
    cached ones attends to."""
    return q_len * past + q_len * (q_len + 1) // 2


def attn_flops(cfg: Dict, pairs: int) -> float:
    """Forward score and context products over ``pairs`` (q, k) pairs."""
    if cfg["model_type"] != "qwen3":
        return 0.0
    _, H, _, hd, _ = _attn_shape(cfg)
    return 4.0 * H * hd * pairs * cfg["num_hidden_layers"]


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
    three times the forward attention (or WKV) products."""
    tokens = batch * seq
    flops = 6.0 * matmul_params(cfg) * tokens
    if cfg["model_type"] == "qwen3":
        flops += 3.0 * attn_flops(cfg, batch * causal_pairs(seq))
    elif cfg["model_type"] == "rwkv6":
        flops += 3.0 * cfg["num_hidden_layers"] * wkv_fwd_flops(
            batch, seq, cfg["num_attention_heads"], cfg["head_size"])
    return flops


def serve_flops(cfg: Dict, tokens: int, pairs: int) -> float:
    """Forward over ``tokens`` tokens that attend to ``pairs`` pairs."""
    return 2.0 * matmul_params(cfg) * tokens + attn_flops(cfg, pairs)


def decode_step_bytes(cfg: Dict, contexts: Iterable[int],
                      weight_bytes: int = 2, kv_bytes: int = 2) -> float:
    """HBM bytes one decode step needs: every weight once, and the keys and
    values of the live positions of the live slots only."""
    D, H, KV, hd, F = _attn_shape(cfg)
    per_pos = 2 * cfg["num_hidden_layers"] * KV * hd * kv_bytes
    return param_count(cfg) * weight_bytes + per_pos * sum(contexts)
