"""RWKV-6 "Finch" (``model_type`` ``rwkv6``): attention-free layers of
time mixing (the WKV recurrence with data-dependent decay) and channel
mixing, every layer alike.

What the benchmark knows of the model type: see ``archs/qwen3.py``.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from chipbench import flops, weights

WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "head_size": "rwkv_head_dim", "intermediate_size": "d_ff",
          "vocab_size": "vocab", "tie_word_embeddings": "tie_embeddings",
          "rms_norm_eps": "norm_eps"}


def _decay_base(key, shape):
    return jax.random.uniform(key, shape, jnp.float32, -6.0, -1.0)


WEIGHTS = {
    **{name: weights.norm for name in ("ln1", "ln2", "ln_x", "wd_b")},
    **{f"dd_b_{n}": weights.norm for n in "rkvgw"},
    **{name: weights.unit for name in ("mu_r", "mu_k", "mu_v", "mu_g",
                                       "mu_w", "mu_k2", "mu_r2")},
    "w0": _decay_base,
    "u": lambda key, shape: 0.5 * jax.random.normal(key, shape, jnp.float32),
}


def groups(cfg: Dict):
    from repro.models.config import LayerGroup
    return (LayerGroup(("rwkv",), cfg["num_hidden_layers"]),)


def layer_matmul_params(cfg: Dict) -> int:
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    Dr = cfg["num_attention_heads"] * cfg["head_size"]
    mix, dec = cfg["time_mix_extra_dim"], cfg["time_decay_extra_dim"]
    # r, k, v, g and the output; five data-dependent lerps (each a
    # D->mix->D low-rank pair); the decay's D->dec->Dr pair; channel mix
    return (4 * D * Dr + Dr * D + 5 * (D * mix + mix * D)
            + D * dec + dec * Dr + 2 * D * F + D * D)


def param_count(cfg: Dict) -> int:
    """Every parameter of the published layer: the matmul weights, two
    norms, seven mixing vectors, and the decay base, bonus and group norm
    of the WKV heads; the embedding and the head."""
    D = cfg["hidden_size"]
    Dr = cfg["num_attention_heads"] * cfg["head_size"]
    layer = layer_matmul_params(cfg) + 9 * D + 3 * Dr
    head = 1 if cfg["tie_word_embeddings"] else 2
    return cfg["num_hidden_layers"] * layer + head * cfg["vocab_size"] * D + D


def attn_flops(cfg: Dict, pairs: int) -> float:
    return 0.0


def train_step_flops(cfg: Dict, batch: int, seq: int) -> float:
    return (6.0 * flops.matmul_params(cfg) * batch * seq
            + 3.0 * cfg["num_hidden_layers"] * flops.wkv_fwd_flops(
                batch, seq, cfg["num_attention_heads"], cfg["head_size"]))
