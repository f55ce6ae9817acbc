"""Production meshes.

Functions (not module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS *before* first jax
init and only then builds meshes.

Single pod: ``(data=16, model=16)`` — 256 chips (one v5e pod).
Multi-pod:  ``(pod=2, data=16, model=16)`` — 512 chips across DCN; the
``pod`` axis carries pure data parallelism (gradient all-reduce over DCN),
``data`` carries ZeRO sharding, ``model`` carries TP/EP.

Every axis is ``Auto``: the sharding rules (``launch.shardings``) steer
GSPMD with ``with_sharding_constraint``, which only accepts Auto axes
(``jax.make_mesh`` defaults to Explicit).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str],
              devices=None) -> jax.sharding.Mesh:
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(*, dp: int = 1, tp: int = 1) -> jax.sharding.Mesh:
    """``(data=dp, model=tp)`` over this host's devices."""
    return make_mesh((dp, tp), ("data", "model"))


def dp_axes(mesh: jax.sharding.Mesh) -> Tuple[str, ...]:
    """The batch-sharding axes: ('pod','data') on multi-pod, ('data',) else."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def tp_size(mesh: jax.sharding.Mesh) -> int:
    return mesh.shape.get("model", 1)
