"""A configuration that states a mesh: the training driver builds the
program on it, a whole run on it compares with the reference, a run whose
exchange between chips is left out is not correct, the reference split over
the chips reads as on one, and the faults ``control.py`` plants in the
reference put in the program's place are not correct.

The mesh runs in a child process whose CPU backend has four host devices
(``--xla_force_host_platform_device_count``), as the tests' own process
must see one.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

import tinycell  # noqa: E402

CELL = "qwen3-4b-2x2.train.zero3"
MESH = {"data": 2, "model": 2}

CHILD = r"""
import json, math, shutil, sys
sys.path.insert(0, sys.argv[1])
import tinycell
from chipbench.harness import load_module

drv = load_module("drivers", "train")
limits = json.loads(sys.argv[3])


def cell(mesh, batch=4):
    c = tinycell.cell("train", limits)
    c.traffic = dict(c.traffic, batch=batch, evaluator_hz=0)
    if mesh:
        c.config["mesh"] = mesh
        c.chips = 4
    return c


def two_steps(mesh):
    s = tinycell.session(cell(mesh), seed=31)
    trainer, state, ckpt = drv.build(s)
    try:
        state = drv.advance(trainer, state, 2)
        leaf = state["params"]["g0"]["s0"]["w_gate"]
        return {"losses": [m["loss"] for m in trainer.metrics_log],
                "devices": len(leaf.sharding.device_set),
                "shard": list(leaf.addressable_shards[0].data.shape),
                "opt": len(state["opt"]["m"]["g0"]["s0"]["w_gate"]
                           .sharding.device_set)}
    finally:
        trainer.shutdown()
        shutil.rmtree(ckpt, ignore_errors=True)


def no_exchange(bb, opt_cfg, settings):
    # each device takes the loss and gradient of its own rows with the
    # whole model; nothing is exchanged, and one device's answer is kept
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.models import Backbone
    from repro.optim import adamw
    plain = Backbone(bb.cfg, remat=False)

    def step(state, batch):
        local = lambda p, b: jax.value_and_grad(plain.loss_fn)(p, b)
        loss, grads = jax.shard_map(
            local, mesh=bb.mesh, in_specs=(P(), P(("data", "model"))),
            out_specs=(P(), P()), check_vma=False)(state["params"], batch)
        params, opt, metrics = adamw.apply_updates(
            opt_cfg, state["params"], state["opt"], grads)
        return {"params": params, "opt": opt}, dict(metrics, loss=loss)
    return step


def whole_run():
    out = drv.run(tinycell.session(cell(MESH), seed=17, seconds=0.3))
    return {"failed": out.failed, "checks": out.checks}


def references():
    # the sound reference on one device and split over four; the faults
    # control.py plants in it, over four
    import control
    from chipbench import trainref
    s = tinycell.session(cell(MESH, batch=8), seed=37)
    one = drv.reference(tinycell.session(cell(None, batch=8), seed=37))
    four = drv.reference(s)
    out = {"one": one, "four": four}
    for name in ("half_batch", "no_exchange"):
        with control.planted(getattr(control, name)):
            out[name] = trainref.compare(drv.reference(s), four)
    return out


MESH = json.loads(sys.argv[4])
if sys.argv[2] == "two_steps":
    print(json.dumps({"one": two_steps(None), "mesh": two_steps(MESH)}))
elif sys.argv[2] == "references":
    print(json.dumps(references()))
else:
    sound = whole_run()
    from repro.runtime import train_loop
    train_loop.make_train_step = no_exchange
    print(json.dumps({"sound": sound, "no_exchange": whole_run()}))
"""


def child(mode: str) -> dict:
    with open(os.path.join(os.path.dirname(HERE), "limits",
                           f"{CELL}.json")) as f:
        limits = f.read()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", CHILD, HERE, mode, limits,
                        json.dumps(MESH)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def correct(run: dict) -> bool:
    return run["failed"] == 0 and all(v <= lim for v, lim in
                                      run["checks"].values())


def test_a_mesh_config_trains_on_four_devices_as_on_one():
    """A tiny qwen3 with ``"mesh": {"data": 2, "model": 2}``: two steps
    through the driver, its state laid out over the four devices (ZeRO-3
    over ``data``, TP over ``model``), its first-step loss within the
    chip smoke's 2e-2 of the one-device build's."""
    out = child("two_steps")
    one, mesh = out["one"], out["mesh"]
    assert len(one["losses"]) == len(mesh["losses"]) == 2
    assert (one["devices"], mesh["devices"], mesh["opt"]) == (1, 4, 4)
    # w_gate [layers, D, F]: D over data, F over model
    assert one["shard"] == [2, 64, 128] and mesh["shard"] == [2, 32, 64]
    a, b = mesh["losses"][0], one["losses"][0]
    assert abs(a - b) / abs(b) <= 2e-2


def test_a_mesh_run_whose_exchange_is_left_out_is_not_correct():
    """A whole run of the tiny mesh cell under the new cell's limits is
    correct; with the step's exchange between chips left out (each device
    answers from its own rows) it is not."""
    out = child("runs")
    assert correct(out["sound"]), out["sound"]
    assert not correct(out["no_exchange"]), out["no_exchange"]


def test_the_reference_split_over_four_devices_reads_as_on_one():
    """The reference's rows split over four devices and taken on one: the
    same losses and per-leaf first-gradient norms, within float32 rounding;
    and each fault ``control.py --faults`` plants in it (half of the batch
    left out; the exchange between the devices left out) fails a limit of
    the new cell."""
    out = child("references")
    one, four = out["one"], out["four"]
    assert four["loss"] == pytest.approx(one["loss"], rel=1e-5)
    assert set(four["grad"]) == set(one["grad"])
    for k, v in one["grad"].items():
        assert four["grad"][k] == pytest.approx(v, rel=1e-4, abs=1e-7), k
    with open(os.path.join(os.path.dirname(HERE), "limits",
                           f"{CELL}.json")) as f:
        limits = json.load(f)
    for fault in ("half_batch", "no_exchange"):
        assert any(out[fault][k] > lim for k, lim in limits.items()), \
            (fault, out[fault])
