"""The training reference: the plain model, three steps of AdamW, and the
numbers a training cell compares.

The reference regenerates the weights and batches from the seed and takes
the loss and its gradient in float32 at full precision on the devices: the
cell's chips each hold the whole weights and take an equal share of the
batch's rows, and their gradients are summed, which is the batch's mean
loss and gradient. The optimizer, decoupled-weight-decay Adam with
global-norm clipping and a warm-up plus cosine schedule as the traffic file
states it, runs on the devices one leaf at a time; between steps its
moments wait on the host (weights, gradients and both moments of a whole
model do not fit a chip together). The weights' change is taken against
their start regenerated from the seed, so no copy of the start is kept.

Readings, on both sides:

* ``loss``   each step's loss;
* ``grad``   per leaf (per layer of a stacked leaf), the norm of the first
  gradient as the optimizer got it: first moment after step 1 / (1 - b1);
* ``change`` per leaf, the norm of the weights' change over the steps.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import data, weights

ROWS = "rows"


def lr_at(opt: Dict, step: int) -> float:
    warm = min(step / max(opt["warmup_steps"], 1), 1.0)
    t = min(max((step - opt["warmup_steps"])
                / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0), 1.0)
    cos = opt["min_lr_frac"] + (1 - opt["min_lr_frac"]) * 0.5 * (
        1 + math.cos(math.pi * t))
    return opt["lr"] * warm * cos


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw_leaf(p, g, m, v, scale, lr, b1, b2, b1c, b2c, eps, wd):
    """One AdamW step of one float32 leaf."""
    g = g * scale
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * jnp.square(g)
    delta = (m / b1c) / (jnp.sqrt(v / b2c) + eps) + wd * p
    return p - lr * delta, m, v


@jax.jit
def _sumsq(leaves):
    return sum(jnp.sum(jnp.square(a)) for a in leaves)


@jax.jit
def _norm(a):
    return jnp.sqrt(jnp.sum(jnp.square(a)))


def grad_fn(loss_fn: Callable, treedef, mesh: Mesh) -> Callable:
    """``f(leaves, tokens, labels)`` -> (the batch's mean loss, the mean
    gradient's leaves): the rows split over the mesh's devices, the weights
    whole on each; the gradient is summed across the devices."""
    tree = lambda leaves: jax.tree_util.tree_unflatten(treedef, leaves)
    whole = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(ROWS))
    return jax.jit(jax.value_and_grad(lambda p, t, l: loss_fn(tree(p), t, l)),
                   in_shardings=(whole, rows, rows),
                   out_shardings=(whole, whole))


def run(ref, cfg: Dict, seed: int, batch: int, seq: int, opt: Dict,
        prec: str = "f32", steps: int = 3, devices: Sequence = ()) -> Dict:
    """The reference's readings over the first ``steps`` steps, its rows
    split over ``devices`` (the first device where none are given)."""
    devices = list(devices) or jax.devices()[:1]
    if batch % len(devices):
        raise ValueError(f"a batch of {batch} rows does not split over "
                         f"{len(devices)} devices")
    mesh = Mesh(np.asarray(devices), (ROWS,))
    whole = NamedSharding(mesh, P())
    rows = NamedSharding(mesh, P(ROWS))
    get = weights.leaf_fn(seed, cfg["model_type"], sharding=whole)
    flat, treedef = jax.tree_util.tree_flatten(ref.init(get, cfg))
    keyed = lambda leaves: ref.keys(jax.tree_util.tree_unflatten(treedef,
                                                                 leaves))
    grads = grad_fn(lambda p, t, l: ref.loss(p, t, l, cfg, prec), treedef,
                    mesh)
    print(f"[ref] loss and gradient over {len(devices)} device(s), "
          f"{batch // len(devices)} rows each", flush=True)
    m = v = None
    losses, grad = [], None
    for step in range(1, steps + 1):
        b = data.train_batch(seed, step - 1, batch, seq, cfg["vocab_size"])
        loss, g = grads(flat, jax.device_put(b["tokens"], rows),
                        jax.device_put(b["labels"], rows))
        losses.append(float(loss))
        g = jax.tree_util.tree_leaves(g)
        gnorm = math.sqrt(float(_sumsq(g)))
        hyper = [np.float32(x) for x in (
            min(1.0, opt["clip_norm"] / (gnorm + 1e-9)), lr_at(opt, step),
            opt["b1"], opt["b2"], 1 - opt["b1"] ** step,
            1 - opt["b2"] ** step, opt["eps"], opt["weight_decay"])]
        first, next_m, next_v = [], [], []
        for i in range(len(flat)):
            if m is None:
                mi = jnp.zeros(flat[i].shape, jnp.float32, device=whole)
                vi = jnp.zeros(flat[i].shape, jnp.float32, device=whole)
            else:
                mi, vi = jax.device_put((m[i], v[i]), whole)
            flat[i], mi, vi = _adamw_leaf(flat[i], g[i], mi, vi, *hyper)
            g[i] = None
            if step == 1:
                first.append(float(_norm(mi)) / (1.0 - opt["b1"]))
            if step < steps:              # the moments wait on the host
                next_m.append(np.asarray(mi))
                next_v.append(np.asarray(vi))
        m, v = next_m, next_v
        if step == 1:
            grad = keyed(first)
    change = {}
    for k, a in keyed(flat).items():
        path, _, layer = k.partition("@")
        change[k] = float(_norm(a - get(path, int(layer or 0), a.shape)))
    return {"loss": losses, "grad": grad, "change": change}


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The widest gap of each reading between the program and the
    reference. A cell's limits file names the ones it compares.

    Norm gaps are taken leaf by leaf against the larger of that leaf's
    reference norm and the median leaf's. Leaves whose reference gradient
    is under a thousandth of the median leaf's move by round-off alone and
    are left out of ``change``.
    """
    if set(prog["grad"]) != set(ref["grad"]) or \
            set(prog["change"]) != set(ref["change"]):
        missing = set(ref["grad"]) ^ set(prog["grad"])
        raise ValueError(f"leaves differ between program and reference: "
                         f"{sorted(missing)[:8]}")
    loss = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"], ref["loss"]))
    med_g = float(np.median(list(ref["grad"].values())))
    moved = {k for k, x in ref["grad"].items() if x >= 1e-3 * med_g}
    med_c = float(np.median([ref["change"][k] for k in moved]))

    def gap(a: Dict, b: Dict, keys, med: float) -> float:
        return max(abs(a[k] - b[k]) / max(b[k], med) for k in keys)

    return {"loss": loss,
            "grad": gap(prog["grad"], ref["grad"], ref["grad"], med_g),
            "change": gap(prog["change"], ref["change"], moved, med_c)}


def widest(prog: Dict, ref: Dict, n: int = 3) -> Dict[str, list]:
    """The ``n`` leaves of widest gap per reading: (key, program, reference)
    -- what a reader of a failed check looks at first."""
    out = {}
    for what in ("grad", "change"):
        med = float(np.median(list(ref[what].values())))
        keys = sorted(ref[what], key=lambda k: -abs(prog[what][k] - ref[what][k])
                      / max(ref[what][k], med))
        out[what] = [(k, prog[what][k], ref[what][k]) for k in keys[:n]]
    return out
