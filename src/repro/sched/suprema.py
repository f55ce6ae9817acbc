"""Suprema derivation for training-step transactions (DESIGN.md §2.2).

OptSVA-CF's early release depends on *a-priori knowledge* of access counts
(paper §2.2: suprema from the programmer, a type checker, or static
analysis). For a training step this knowledge is exact and derivable from
the model structure — this module is the "static analyzer" for our domain:

* each layer-block's weights are **read** once in forward, once in backward,
  and once more where the backward recomputes the layer (full remat; under
  the saving policy it recomputes none of the layer's matmuls);
* each block's gradient is **written** once, at a known point in backward
  (→ release the gradient object immediately after: the per-layer
  reduce-scatter schedule);
* the optimizer **updates** each parameter exactly once per step.

``step_suprema`` returns these bounds per parameter group; the transactional
store uses them to declare trainer transactions, and the overlap schedule in
``launch.shardings`` is their data-plane transcription (weight all-gather =
asynchronous read-only buffering; per-layer grad reduce-scatter = early
release on last write).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Union

from repro.core.api import Suprema
from repro.models.config import ModelConfig


@dataclass(frozen=True)
class StepAccessPlan:
    """Per-parameter-group access bounds for one training step."""

    weight_reads: int          # forward + backward (+ remat)
    grad_writes: int           # one per step, at last-backward-use
    optimizer_updates: int     # one per step

    def as_suprema(self) -> Suprema:
        return Suprema(reads=self.weight_reads, writes=self.grad_writes,
                       updates=self.optimizer_updates)


def step_suprema(cfg: ModelConfig, *,
                 remat: Union[bool, str] = True
                 ) -> Dict[str, StepAccessPlan]:
    """Exact access bounds per group for one train step. ``remat`` is how
    the step checkpoints its layers: a policy of
    ``repro.models.backbone.REMAT_POLICIES``, True for a plain checkpoint
    (``"full"``), False for none."""
    reads = 3 if remat in (True, "full") else 2  # fwd, (remat-fwd), bwd
    plan: Dict[str, StepAccessPlan] = {}
    for gi, group in enumerate(cfg.groups):
        plan[f"g{gi}"] = StepAccessPlan(reads, 1, 1)
    plan["embed"] = StepAccessPlan(2, 1, 1)   # in-embed + logits head (tied)
    plan["final_norm"] = StepAccessPlan(reads, 1, 1)
    return plan


def release_points(cfg: ModelConfig) -> Dict[str, str]:
    """Where each group's gradient reaches its write supremum — i.e. where
    the early-release (reduce-scatter) fires. Groups release in reverse
    group order during backward; within a scanned group, per-iteration."""
    order = {}
    n = len(cfg.groups)
    for gi in range(n):
        order[f"g{gi}"] = (f"backward scan iteration of group {gi} "
                           f"(fires {n - gi}-th from step end, per layer)")
    return order
