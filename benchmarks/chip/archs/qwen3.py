"""Qwen3 (``model_type`` ``qwen3``): a dense decoder of grouped-query
attention with qk-norm and a SwiGLU MLP, every layer alike.

What the benchmark knows of the model type: the configuration keys that
must equal the program's widths, the program's layer groups, the
operations and parameters the work needs (``chipbench.flops``), and the
weight rules of its leaves that the generic rule does not cover
(``chipbench.weights``).
"""
from __future__ import annotations

from typing import Dict

from chipbench import flops, weights

# configuration-file key -> program ModelConfig field
WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "hd",
          "intermediate_size": "d_ff", "vocab_size": "vocab",
          "tie_word_embeddings": "tie_embeddings",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"}

WEIGHTS = {name: weights.norm for name in ("ln1", "ln2", "q_norm", "k_norm")}


def groups(cfg: Dict):
    from repro.models.config import LayerGroup
    return (LayerGroup(("attn",), cfg["num_hidden_layers"]),)


def _shape(cfg: Dict):
    return (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["intermediate_size"])


def layer_matmul_params(cfg: Dict) -> int:
    D, H, KV, hd, F = _shape(cfg)
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def param_count(cfg: Dict) -> int:
    """Every parameter (tied embedding once)."""
    D, H, KV, hd, F = _shape(cfg)
    layer = layer_matmul_params(cfg) + 2 * D + 2 * hd
    head = 1 if cfg["tie_word_embeddings"] else 2
    return cfg["num_hidden_layers"] * layer + head * cfg["vocab_size"] * D + D


def attn_flops(cfg: Dict, pairs: int) -> float:
    _, H, _, hd, _ = _shape(cfg)
    return 4.0 * H * hd * pairs * cfg["num_hidden_layers"]


def train_step_flops(cfg: Dict, batch: int, seq: int) -> float:
    return (6.0 * flops.matmul_params(cfg) * batch * seq
            + 3.0 * attn_flops(cfg, batch * flops.causal_pairs(seq)))


def cache_bytes_per_position(cfg: Dict, kv_bytes: int) -> int:
    """Keys and values of one position, over every layer."""
    _, _, KV, hd, _ = _shape(cfg)
    return 2 * cfg["num_hidden_layers"] * KV * hd * kv_bytes
