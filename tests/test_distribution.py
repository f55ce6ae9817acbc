"""Distribution-layer tests: sharding rules, suprema plan, mini dry-run.

The production-mesh dry-run needs 512 host devices, which must be set
before jax initializes — so full-mesh checks run in a subprocess; the
in-process tests cover the pure rule functions and a small 4-device mesh.
"""
import json
import os
import subprocess
import sys

import pytest

from repro.launch.roofline import RooflineTerms, chip_peaks
from repro.models import PartitionPlan, get_config

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --------------------------------------------------------------------------- #
# Pure rule functions                                                          #
# --------------------------------------------------------------------------- #
def test_partition_plan_divisibility_all_archs():
    plan = PartitionPlan(tp=16)
    from repro.models.config import ARCH_NAMES
    for arch in ARCH_NAMES:
        cfg = get_config(arch)
        plan.check(cfg)
        assert plan.eff_heads(cfg) % 16 == 0
        assert plan.eff_kv_heads(cfg) % 16 == 0
        assert plan.eff_vocab(cfg) % 16 == 0
        # replication must be group-consistent (exactness criterion)
        kv_map = plan.kv_graft_map(cfg)
        g_new = plan.eff_heads(cfg) // plan.eff_kv_heads(cfg)
        g_orig = cfg.n_heads // cfg.n_kv_heads
        for i in range(cfg.n_heads):
            assert kv_map[i // g_new] == i // g_orig, (arch, i)


def test_step_suprema_exact_counts():
    from repro.sched import step_suprema
    cfg = get_config("gemma2-2b")
    plan = step_suprema(cfg, remat=True)
    assert plan["g0"].weight_reads == 3       # fwd + remat + bwd
    assert plan["g0"].grad_writes == 1
    assert plan["g0"].optimizer_updates == 1
    sup = plan["g0"].as_suprema()
    assert sup.total == 5


@pytest.mark.parametrize("remat,reads", [("dots", 2), ("full", 3),
                                         (True, 3), (False, 2)])
def test_step_suprema_weight_reads_follow_the_remat_policy(remat, reads):
    """A backward that recomputes no projection reads each layer's weights
    twice (forward, backward); full remat reads them a third time."""
    from repro.sched import step_suprema
    plan = step_suprema(get_config("qwen3-4b"), remat=remat)
    assert plan["g0"].weight_reads == reads
    assert plan["g0"].as_suprema().total == reads + 2


def test_roofline_terms_dominant_and_fraction():
    peaks = chip_peaks("TPU v5 lite")
    t = RooflineTerms(compute_s=0.5, memory_s=0.2, collective_s=0.8,
                      model_flops=peaks.flops * 0.4 * 256, hlo_flops=1e14,
                      useful_ratio=0.5, peaks=peaks, n_chips=256)
    assert t.dominant == "collective"
    assert t.roofline_fraction == pytest.approx(0.4 / 0.8)


def test_roofline_peaks_refuse_unknown_device_kind():
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")


# --------------------------------------------------------------------------- #
# Subprocess mini dry-run on the real production meshes                       #
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mesh", ["single", "multi"])
def test_dryrun_cell_compiles_on_production_mesh(mesh, tmp_path):
    """whisper-tiny × train_4k lowers + compiles on 256/512 fake devices."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
from repro.launch.dryrun import run_cell
from repro.runtime.steps import StepSettings
res = run_cell("whisper-tiny", "train_4k", "{mesh}",
               settings=StepSettings(), verbose=False)
print(json.dumps({{"chips": res["chips"],
                   "flops": res["roofline"]["hlo_flops"],
                   "coll": res["hlocost"]["collective_bytes"]}}))
"""
    out = subprocess.run([sys.executable, "-c", code],
                         env={**os.environ, "PYTHONPATH": SRC},
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-2000:]
    data = json.loads(out.stdout.strip().splitlines()[-1])
    assert data["chips"] == (512 if mesh == "multi" else 256)
    assert data["flops"] > 0 and data["coll"] > 0


def test_long500k_skips_full_attention():
    from repro.launch.dryrun import cell_skip_reason
    from repro.models import SHAPES
    assert cell_skip_reason("qwen2-7b", SHAPES["long_500k"]) is not None
    assert cell_skip_reason("rwkv6-3b", SHAPES["long_500k"]) is None
    assert cell_skip_reason("recurrentgemma-9b", SHAPES["long_500k"]) is None
    assert cell_skip_reason("mixtral-8x22b", SHAPES["long_500k"]) is None
    assert cell_skip_reason("qwen2-7b", SHAPES["train_4k"]) is None
