"""The harness finds a cell's files by name, refuses unknown names, takes a
new configuration, mix and metric from new files and entries alone, and
exits non-zero with no result where no TPU is present."""
import json
import os
import shutil
import subprocess
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)

from chipbench import harness  # noqa: E402
from chipbench.harness import BenchError, Cell  # noqa: E402


def bench() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_every_cell_finds_its_files_and_readers():
    b = bench()
    for w in b["workloads"]:
        c = Cell.find(w["name"], b)
        assert harness.arch(c.config["model_type"]).WIDTHS
        harness.load_module("drivers", c.traffic["kind"])
        harness.load_module("refs", c.config["reference"])
        assert c.limits and all(v > 0 for v in c.limits.values())
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer
        for m in c.per_layer:
            assert callable(harness.reader(m["name"]).read)
            assert m["moves"] in names


def test_a_part_of_a_split_metric_reads_as_its_metric():
    """``<metric>.<part>`` without a reader or driver value of its own is
    ``<metric>``'s; with one, its own; with neither, refused."""
    assert harness.reader("mfu.train.mesh").__file__ == \
        harness.reader("mfu.train").__file__
    assert harness.reader("mfu.train").__file__.endswith("mfu.train.py")
    values = {"train_tokens_per_s": 5.0, "setup_s.x": 2.0}
    assert harness.driver_value(values, "train_tokens_per_s.mesh") == 5.0
    assert harness.driver_value(values, "setup_s.x") == 2.0
    with pytest.raises(BenchError):
        harness.reader("no_such_metric.mesh")


@pytest.mark.parametrize("kind,name", [("drivers", "nope"),
                                       ("metrics", "no_such_metric"),
                                       ("refs", "llama")])
def test_unknown_module_names_are_refused(kind, name):
    with pytest.raises(BenchError):
        harness.load_module(kind, name)


def test_unknown_workload_config_or_mix_is_refused():
    b = bench()
    with pytest.raises(BenchError):
        Cell.find("no.such.cell", b)
    w = dict(b["workloads"][0], name="x.bad-config", config="nope")
    with pytest.raises(BenchError):
        Cell.find("x.bad-config", dict(b, workloads=b["workloads"] + [w]))
    w = dict(b["workloads"][0], name="x.bad-mix", traffic="nope")
    with pytest.raises(BenchError):
        Cell.find("x.bad-mix", dict(b, workloads=b["workloads"] + [w]))


# A model type the benchmark has never seen: two layer groups of the
# program's ``moe`` FFN (a stacked 3-D expert leaf), one attention layer,
# then a period of (attention, windowed attention).
TOY_ARCH = """
from chipbench import flops, weights

WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "hd",
          "moe_intermediate_size": "moe_d_ff", "num_experts": "n_experts",
          "num_experts_per_tok": "top_k", "vocab_size": "vocab"}

WEIGHTS = {name: weights.norm for name in ("ln1", "ln2", "q_norm", "k_norm")}


def groups(cfg):
    from repro.models.config import LayerGroup
    first = cfg["num_dense_layers"]
    return (LayerGroup(("attn",), first),
            LayerGroup(("attn", "local"), (cfg["num_hidden_layers"] - first)
                       // 2))


def _attn(cfg):
    D, H, KV, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return D * H * hd + 2 * D * KV * hd + H * hd * D


def layer_matmul_params(cfg):
    D, E, k = cfg["hidden_size"], cfg["num_experts"], \
        cfg["num_experts_per_tok"]
    return _attn(cfg) + D * E + k * 3 * D * cfg["moe_intermediate_size"]


def param_count(cfg):
    D, hd, E = cfg["hidden_size"], cfg["head_dim"], cfg["num_experts"]
    layer = (_attn(cfg) + D * E + E * 3 * D * cfg["moe_intermediate_size"]
             + 2 * D + 2 * hd)
    return cfg["num_hidden_layers"] * layer + cfg["vocab_size"] * D + D


def attn_flops(cfg, pairs):
    return (4.0 * cfg["num_attention_heads"] * cfg["head_dim"] * pairs
            * cfg["num_hidden_layers"])


def train_step_flops(cfg, batch, seq):
    return (6.0 * flops.matmul_params(cfg) * batch * seq
            + 3.0 * attn_flops(cfg, batch * flops.causal_pairs(seq)))
"""

TOY_CONFIG = {"model_type": "toy_moe", "program_arch": "toymoe-tiny",
              "reference": "qwen3", "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "moe_intermediate_size": 32, "num_experts": 4,
              "num_experts_per_tok": 2, "vocab_size": 512,
              "num_dense_layers": 1, "num_hidden_layers": 3}


def test_new_config_mix_and_metric_need_only_new_files(tmp_path, monkeypatch):
    """A copy of the benchmark gains a configuration, a traffic mix and a
    per-layer metric by new files and new entries; and a configuration of
    a model type it has never seen, with two layer groups and a stacked
    expert leaf, through a new ``archs/`` file, which is then built, drawn
    and counted. No file is edited."""
    root = tmp_path / "checkout"
    chip = root / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    before = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()}
    b = bench()
    cfg = json.loads((chip / "configs" / "qwen3-4b-L5.json").read_text())
    (chip / "configs" / "qwen3-4b-L3.json").write_text(
        json.dumps(dict(cfg, num_hidden_layers=3)))
    (chip / "traffic" / "train-b8.json").write_text(json.dumps(
        dict(json.loads((chip / "traffic" / "train-b4-eval.json").read_text()),
             batch=8, evaluator_hz=0)))
    (chip / "limits" / "qwen3-4b-L3.train.b8.json").write_text(
        json.dumps({"loss": 0.02, "grad": 0.01, "change": 0.05}))
    (chip / "metrics" / "step_ms.train.py").write_text(
        "def read(run):\n    return 7.0\n")
    b["configs"].append(dict(b["configs"][0], name="qwen3-4b-L3",
                             file="benchmarks/chip/configs/qwen3-4b-L3.json"))
    b["workloads"].append({"name": "qwen3-4b-L3.train.b8",
                           "config": "qwen3-4b-L3", "traffic": "train-b8",
                           "chips": 1, "why": "x"})
    for m in b["end_to_end"]:
        if m["name"] == "train_tokens_per_s":
            m["workloads"].append("qwen3-4b-L3.train.b8")
    b["per_layer"].append({"name": "step_ms.train", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "runtime", "moves": "train_tokens_per_s",
                           "workloads": ["qwen3-4b-L3.train.b8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    monkeypatch.setattr(harness, "HERE", chip)
    monkeypatch.setattr(harness, "ROOT", root)

    c = Cell.find("qwen3-4b-L3.train.b8")
    assert c.config["num_hidden_layers"] == 3
    assert c.traffic["batch"] == 8 and c.traffic["kind"] == "train"
    assert [m["name"] for m in c.per_layer] == ["step_ms.train"]
    assert [m["name"] for m in c.end_to_end] == ["train_tokens_per_s",
                                                  "setup_s"]
    assert harness.load_module("metrics", "step_ms.train").read({}) == 7.0
    assert harness.load_module("drivers", c.traffic["kind"]).run

    # the new model type: its file, its configuration, a cell on it
    import dataclasses
    import jax
    import numpy as np
    from chipbench import flops, program, weights
    from repro.models import Backbone, get_config
    from repro.models.config import register
    register(dataclasses.replace(
        get_config("qwen3-4b"), name="toymoe-tiny", family="moe", d_model=64,
        n_heads=4, n_kv_heads=2, head_dim=16, d_ff=128, vocab=512,
        ffn_kind="moe", n_experts=4, top_k=2, moe_d_ff=32, attn_window=8))
    (chip / "archs" / "toy_moe.py").write_text(TOY_ARCH)
    (chip / "configs" / "toy-moe.json").write_text(json.dumps(TOY_CONFIG))
    (chip / "limits" / "toy-moe.train.b8.json").write_text(
        json.dumps({"loss": 0.02, "grad": 0.01, "change": 0.05}))
    b["configs"].append(dict(b["configs"][0], name="toy-moe",
                             file="benchmarks/chip/configs/toy-moe.json"))
    b["workloads"].append({"name": "toy-moe.train.b8", "config": "toy-moe",
                           "traffic": "train-b8", "chips": 1, "why": "x"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    c = Cell.find("toy-moe.train.b8")
    pcfg = program.model_config(c.config)
    assert [(g.pattern, g.repeat) for g in pcfg.groups] == [
        (("attn",), 1), (("attn", "local"), 1)]
    specs = Backbone(pcfg).param_specs()
    tree = weights.program_tree(specs, 11, "toy_moe", jax.numpy.float32)
    expert = tree["g1"]["s1"]["w_gate"]              # [repeat, E, D, Fe]
    assert expert.shape == (1, 4, 64, 32)
    assert float(np.std(expert)) == pytest.approx(1 / 8, rel=0.1)
    n = sum(a.size for a in jax.tree_util.tree_leaves(tree))
    assert flops.param_count(c.config) == n
    assert flops.train_step_flops(c.config, 8, 16) == pytest.approx(
        # attention 3 x 4096, router 64 x 4, 2 of 4 experts routed; head;
        # score and context over 8 x 136 causal pairs in 3 layers
        6 * (3 * (12288 + 64 * 4 + 2 * 3 * 64 * 32) + 64 * 512) * 8 * 16
        + 3 * 4 * 64 * 8 * 136 * 3)
    after = {p: p.read_bytes() for p in chip.rglob("*") if p.is_file()
             and p in before}
    assert after == before


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/chip/bench.py", "--workload",
         "qwen3-4b.train.eval", "--seed", "3000000000", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=240)


def test_no_tpu_means_no_result_and_a_nonzero_exit():
    r = _run(ROOT)
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout and "needs 1 TPU" in r.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0 and '"metrics"' not in r.stdout


def test_collector_pauses_are_clocked_by_generation():
    import gc
    clock = harness.GcClock()
    gc.callbacks.append(clock)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(clock)
    assert [g for g, _, _ in clock.pauses] == [2]
    assert clock.summary(clock.pauses[0][1]).startswith("gen2 1 (longest")


def test_the_interpreter_held_up_is_kept_and_a_late_tick_is_not():
    clock = harness.HoldClock()
    for t in (10.0, 10.05, 10.2, 11.0, 11.05):     # 0.75 s late at 10.2
        clock.observe(t)
    assert clock.holds == [(10.2, pytest.approx(0.75))]
    assert clock.summary(10.0) == "1 over 0.5s (longest 0.750s at +0.20s)"
    clock.start()
    clock.stop()                                   # the thread has ended
    assert not clock._thread.is_alive()


def test_host_counters_are_read_where_the_host_has_them():
    before = harness.host_counters()
    sum(range(10 ** 5))
    after = harness.host_counters()
    if os.path.exists("/proc/stat"):
        assert {"steal", "iowait"} <= set(before)
    assert set(before) == set(after)
    assert all(after[k] >= before[k] >= 0 for k in before)
