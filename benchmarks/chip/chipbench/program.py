"""The system under test as the benchmark builds it: the program's own
configuration of the named architecture, at the depth the configuration
file gives, after checking that every width in the file is the program's.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

# configuration-file key -> program ModelConfig field, per model type
WIDTHS = {
    "qwen3": {"hidden_size": "d_model", "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "hd",
              "intermediate_size": "d_ff", "vocab_size": "vocab",
              "tie_word_embeddings": "tie_embeddings",
              "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps"},
    "rwkv6": {"hidden_size": "d_model", "num_attention_heads": "n_heads",
              "head_size": "rwkv_head_dim", "intermediate_size": "d_ff",
              "vocab_size": "vocab", "tie_word_embeddings": "tie_embeddings",
              "rms_norm_eps": "norm_eps"},
}


def model_config(cfg: Dict):
    """The program's ModelConfig for a configuration file."""
    from repro.models import get_config
    from repro.models.config import LayerGroup
    base = get_config(cfg["program_arch"])
    for key, attr in WIDTHS[cfg["model_type"]].items():
        if getattr(base, attr) != cfg[key]:
            raise ValueError(f"{cfg['program_arch']}: {attr} is "
                             f"{getattr(base, attr)!r}, the configuration's "
                             f"{key} is {cfg[key]!r}")
    (group,) = base.groups
    return dataclasses.replace(
        base, groups=(LayerGroup(group.pattern, cfg["num_hidden_layers"]),))
