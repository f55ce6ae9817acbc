"""Weights made from the seed: every leaf of the benchmark's training
configurations holds the values recorded before each model type's weight
rules moved into ``archs/``, and the rules are found by leaf name."""
import json
import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

from chipbench import harness, program, weights  # noqa: E402

with open(os.path.join(CHIP, "tests", "data", "seeded_weights.json")) as f:
    RECORDED = json.load(f)


@pytest.mark.parametrize("name", sorted(RECORDED["configs"]))
def test_every_seeded_leaf_is_the_recorded_one(name):
    """Per leaf and layer: the sum of squares and the first three and the
    last value, each within float32 rounding of the recording (a changed
    rule, key or scale moves them by far more)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import Backbone
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        cfg = json.load(f)
    want = RECORDED["configs"][name]
    key = weights.base_key(RECORDED["seed"])
    mt = cfg["model_type"]
    specs = Backbone(program.model_config(cfg)).param_specs()
    got = {}
    for p, s in jax.tree_util.tree_flatten_with_path(specs)[0]:
        path = weights._path_str(p)
        if path.startswith("g"):       # as program_tree draws a stacked leaf
            layers = np.asarray(jax.jit(lambda k: jax.vmap(
                lambda l: weights.leaf(k, mt, path, l, s.shape[1:]))(
                jnp.arange(s.shape[0])))(key))
        else:
            layers = np.asarray(jax.jit(
                lambda k: weights.leaf(k, mt, path, 0, s.shape))(key))[None]
        for l, a in enumerate(layers):
            a = a.reshape(-1)
            got[f"{path}@{l}"] = [float(np.sum(np.square(a, dtype=np.float64))),
                                  *map(float, a[:3]), float(a[-1])]
        del layers
    assert set(got) == set(want)
    for k in want:
        assert got[k] == pytest.approx(want[k], rel=1e-6), k


def test_a_leaf_of_two_axes_or_more_is_scaled_by_its_input_width():
    import jax
    import numpy as np
    key = jax.random.PRNGKey(3)
    expert = weights._draw(key, "qwen3", "w_gate", (8, 16, 4))
    np.testing.assert_allclose(
        expert, jax.random.normal(key, (8, 16, 4)) / 4.0, rtol=1e-6)
    dense = weights._draw(key, "qwen3", "w_new", (9, 5))
    np.testing.assert_allclose(dense, jax.random.normal(key, (9, 5)) / 3.0,
                               rtol=1e-6)
    with pytest.raises(KeyError, match="archs/qwen3.py"):
        weights._draw(key, "qwen3", "expert_bias", (8,))


def test_a_model_type_draws_by_its_own_rules_alone():
    """A rule one model type names applies to none other: rwkv6's bonus
    ``u`` is drawn for rwkv6, and is no leaf of qwen3's."""
    import jax
    key = jax.random.PRNGKey(5)
    assert weights.rules("rwkv6")["u"] is harness.arch("rwkv6").WEIGHTS["u"]
    assert "u" not in weights.rules("qwen3")
    assert weights.rules("qwen3")["ln1"] is weights.norm
    assert weights._draw(key, "rwkv6", "u", (4,)).shape == (4,)
    with pytest.raises(KeyError, match="archs/qwen3.py"):
        weights._draw(key, "qwen3", "u", (4,))
