"""The operation and byte counts against hand counts at tiny shapes, the
parameter count against the program's own tree, and the counts of the
benchmark's configurations against those recorded before each model
type's counts moved into ``archs/``."""
import json
import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

from chipbench import flops  # noqa: E402
from chipbench.harness import BenchError  # noqa: E402

QWEN = {"model_type": "qwen3", "hidden_size": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 2, "intermediate_size": 8,
        "vocab_size": 10, "num_hidden_layers": 3, "tie_word_embeddings": True}
RWKV = {"model_type": "rwkv6", "hidden_size": 4, "num_attention_heads": 2,
        "head_size": 2, "intermediate_size": 8, "vocab_size": 10,
        "num_hidden_layers": 2, "time_mix_extra_dim": 3,
        "time_decay_extra_dim": 5, "tie_word_embeddings": False}


def test_qwen3_layer_and_model_matmul_weights():
    # q 4x4, k and v 4x2 each, o 4x4, gate/up/down 3 x 4x8
    assert flops.layer_matmul_params(QWEN) == 16 + 8 + 8 + 16 + 96
    assert flops.matmul_params(QWEN) == 3 * 144 + 4 * 10


def test_rwkv6_layer_matmul_weights():
    # r,k,v,g 4x4 each, out 4x4, five lerps 4x3+3x4, decay 4x5+5x4,
    # channel mix 4x8 + 8x4 + 4x4
    assert flops.layer_matmul_params(RWKV) == (
        64 + 16 + 5 * 24 + 40 + 64 + 16)


def test_causal_pairs_and_attention():
    assert flops.causal_pairs(3) == 6            # 1 + 2 + 3
    assert flops.causal_pairs(2, past=5) == 13   # 2*5 + 1 + 2
    # QK and PV, 2 FLOPs per multiply-add, 2 heads of 2, 3 layers
    assert flops.attn_flops(QWEN, 6) == 4 * 2 * 2 * 6 * 3
    assert flops.attn_flops(RWKV, 6) == 0


def test_train_step_flops():
    B, S = 2, 3
    assert flops.train_step_flops(QWEN, B, S) == pytest.approx(
        6 * 472 * B * S + 3 * 4 * 2 * 2 * (B * 6) * 3)
    assert flops.train_step_flops(RWKV, B, S) == pytest.approx(
        6 * flops.matmul_params(RWKV) * B * S
        + 3 * 2 * 7 * B * S * 2 * 2 * 2)


def test_wkv_counts():
    assert flops.wkv_fwd_flops(1, 1, 1, 2) == 28
    # r,k,v bf16 and w f32 and y f32 per element; u; both states f32
    assert flops.wkv_fwd_bytes(1, 1, 1, 2, 2, 4) == 2 * (6 + 4 + 4) + 8 + 32


def test_decode_bytes_count_live_positions_only():
    per_pos = 2 * 3 * 1 * 2 * 2                   # k and v, 3 layers
    base = flops.decode_step_bytes(QWEN, [])
    assert base == flops.param_count(QWEN) * 2
    assert flops.decode_step_bytes(QWEN, [5, 7]) == base + 12 * per_pos


def test_param_count_matches_the_programs_tree():
    import jax
    import numpy as np
    from repro.models import Backbone, get_config
    from repro.models.config import LayerGroup
    import dataclasses
    cfg = dataclasses.replace(
        get_config("qwen3-4b"), d_model=64, n_heads=4, n_kv_heads=2,
        head_dim=16, d_ff=96, vocab=128, groups=(LayerGroup(("attn",), 3),))
    tree = Backbone(cfg).param_specs()
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))
    mine = dict(QWEN, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=16, intermediate_size=96,
                vocab_size=128)
    assert flops.param_count(mine) == n


def test_rwkv6_param_count_by_hand():
    # the layer's matmul weights, 9 vectors of D, 3 of Dr = 4; the
    # embedding and the head (untied), the final norm
    layer = flops.layer_matmul_params(RWKV) + 9 * 4 + 3 * 4
    assert flops.param_count(RWKV) == 2 * layer + 2 * 10 * 4 + 4


# Recorded from the counts of chipbench/flops.py as it was before the model
# types' counts moved into archs/: (batch, seq) -> train_step_flops;
# (tokens, pairs) -> serve_flops; decode_step_bytes over contexts 128,
# 1000, 1152; None where the count did not exist for the model type.
RECORDED = {
    "qwen3-4b-L5": {
        "train": [11109453004800.0, 5554726502400.0, 44437812019200.0],
        "params": 893612800,
        "serve": [1873050337280.0, 1910046720.0], "decode": 1833920000},
    "qwen3-4b": {
        "train": [50355203211264.0, 25177601605632.0, 201420812845056.0],
        "params": 4022468096,
        "serve": [8547152691200.0, 8929280000.0], "decode": 8381135872},
    "rwkv6-3b-L4": {
        "train": [6333734584320.0, 3166867292160.0, 25334938337280.0],
        "params": None, "serve": [1050924810240.0, 1026293760.0],
        "decode": None},
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_counts_of_the_configurations_are_those_recorded(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    want = RECORDED[name]
    assert [flops.train_step_flops(cfg, b, s) for b, s in
            ((4, 512), (2, 512), (16, 512))] == want["train"]
    assert [flops.serve_flops(cfg, t, p) for t, p in
            ((1024, flops.causal_pairs(1024)), (1, 1500))] == want["serve"]
    if want["params"] is not None:
        assert flops.param_count(cfg) == want["params"]
        assert flops.decode_step_bytes(cfg, [128, 1000, 1152]) == \
            want["decode"]


def test_an_unknown_model_type_names_the_missing_file():
    with pytest.raises(BenchError, match="archs/lfm2_moe.py"):
        flops.train_step_flops(dict(QWEN, model_type="lfm2_moe"), 1, 8)
