"""Tiny cells for driving the harness on the CPU.

Each registers a small configuration of the program's architecture (widths
cut, structure kept) and builds a ``Cell`` by hand, so a test can run a
whole driver (set-up, window, reference check) in a few seconds.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from chipbench.harness import Cell, Session  # noqa: E402

ADAMW = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "clip_norm": 1.0, "warmup_steps": 10,
         "total_steps": 100000, "min_lr_frac": 0.1}

QWEN = {"model_type": "qwen3", "program_arch": "qwen3-tiny",
        "reference": "qwen3", "hidden_size": 64, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
        "vocab_size": 512, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
        "rope_theta": 1000000, "tie_word_embeddings": True}

RWKV = {"model_type": "rwkv6", "program_arch": "rwkv6-tiny",
        "reference": "rwkv6", "hidden_size": 64, "num_attention_heads": 4,
        "head_size": 16, "intermediate_size": 128, "vocab_size": 512,
        "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "time_mix_extra_dim": 32,
        "time_decay_extra_dim": 64}

TRAIN = {"kind": "train", "batch": 2, "seq": 16, "evaluator_hz": 50,
         "timing_steps": 1, "adamw": ADAMW}
SERVE = {"kind": "serve", "slots": 2, "ctx": 48, "rate_per_s": 20.0,
         "prompt_lens": [8, 16], "output_lens": [2, 4], "check_requests": 3}


def register() -> None:
    from repro.models.config import register as reg
    from repro.models import get_config
    reg(dataclasses.replace(
        get_config("qwen3-4b"), name="qwen3-tiny", d_model=64, n_heads=4,
        n_kv_heads=2, head_dim=16, d_ff=128, vocab=512))
    reg(dataclasses.replace(
        get_config("rwkv6-3b"), name="rwkv6-tiny", d_model=64, n_heads=4,
        n_kv_heads=4, rwkv_head_dim=16, d_ff=128, vocab=512))


def cell(kind: str, limits: dict) -> Cell:
    register()
    config, traffic = {"train": (QWEN, TRAIN), "rwkv": (RWKV, dict(
        TRAIN, evaluator_hz=0)), "serve": (QWEN, SERVE)}[kind]
    return Cell(f"tiny.{kind}", 1, dict(config), dict(traffic), limits,
                [], [])


def session(c: Cell, seed: int = 5, seconds: float = 1.0,
            trace: bool = False) -> Session:
    import jax
    return Session(c, seed, seconds, trace, time.monotonic(), jax.devices())
