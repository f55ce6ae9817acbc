"""Roofline-term derivation from compiled dry-run artifacts.

Three terms per (arch × shape × mesh) cell, all in seconds-per-step on the
target chip, derived from the compiled module (never from wall time):

    compute    = HLO_FLOPs_per_device / peak FLOP/s
    memory     = HLO_bytes_per_device / HBM bytes/s
    collective = collective_bytes_per_device / ICI bytes/s per link

``cost_analysis()`` of a GSPMD-partitioned executable reports the per-device
module, so no extra division by chip count is applied. Collective bytes are
not in cost_analysis: we parse the partitioned HLO text and sum output-shape
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute, tracking which computation (scan body vs top level) each
lives in — the "inside-scan" count is how we verify the early-release
schedule actually moved collectives into the layer loop.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

# ---- per-chip peaks, keyed by jax's ``device_kind`` -------------------------
@dataclass(frozen=True)
class ChipPeaks:
    flops: float     # dense bf16 FLOP/s
    hbm_bw: float    # HBM bytes/s
    ici_bw: float    # inter-chip bytes/s per link


# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links, 50 GB/s
# each).
PEAKS: Dict[str, ChipPeaks] = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
# The chip the production-mesh dry-run compiles for (its placeholder host
# devices report no TPU kind of their own).
DRYRUN_TARGET = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip; a kind with no published entry is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_DTYPE_BYTES = {
    "f64": 8, "s64": 8, "u64": 8,
    "f32": 4, "s32": 4, "u32": 4,
    "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s4": 1, "u4": 1,  # rounded up
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclass
class CollectiveStats:
    bytes_total: int = 0
    count: int = 0
    by_op: Dict[str, int] = field(default_factory=lambda: defaultdict(int))
    by_op_count: Dict[str, int] = field(default_factory=lambda: defaultdict(int))
    in_loop_bytes: int = 0
    in_loop_count: int = 0

    def to_json(self) -> Dict[str, Any]:
        return {
            "bytes_total": self.bytes_total,
            "count": self.count,
            "by_op": dict(self.by_op),
            "by_op_count": dict(self.by_op_count),
            "in_loop_bytes": self.in_loop_bytes,
            "in_loop_count": self.in_loop_count,
        }


def parse_collectives(hlo_text: str) -> CollectiveStats:
    """Sum output bytes of collective ops in a partitioned HLO module."""
    stats = CollectiveStats()
    current_comp = ""
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # computation headers look like:  %body.123 (param...) -> ... {
        if stripped.endswith("{") and ("(" in stripped) and "=" not in stripped.split("(")[0]:
            head = stripped.split("(")[0].strip()
            current_comp = head.lstrip("%")
            continue
        for op in COLLECTIVE_OPS:
            token = f" {op}("
            alt = f" {op}-start("
            if token not in stripped and alt not in stripped:
                continue
            # output shapes appear between '=' and the op name
            eq = stripped.find("=")
            opi = stripped.find(token)
            if opi < 0:
                opi = stripped.find(alt)
            if eq < 0 or opi < eq:
                continue
            out_region = stripped[eq + 1: opi]
            nbytes = sum(_shape_bytes(d, s)
                         for d, s in _SHAPE_RE.findall(out_region))
            stats.bytes_total += nbytes
            stats.count += 1
            stats.by_op[op] += nbytes
            stats.by_op_count[op] += 1
            comp = current_comp.lower()
            if ("while" in comp or "body" in comp or "cond" in comp
                    or "scan" in comp):
                stats.in_loop_bytes += nbytes
                stats.in_loop_count += 1
            break
    return stats


# --------------------------------------------------------------------------- #
# MODEL_FLOPS (the "useful work" yardstick)                                    #
# --------------------------------------------------------------------------- #
def active_param_count(bb) -> Tuple[int, int]:
    """(N_active_nonembed, N_total) from the parameter tree.

    MoE expert leaves are scaled by top_k/n_experts for the active count.
    Embedding table excluded from N_active (a gather, not a matmul); the
    LM head term is added separately by model_flops().
    """
    import jax

    cfg = bb.cfg
    specs = bb.param_specs()
    n_active = 0
    n_total = 0
    moe_frac = (cfg.top_k / cfg.n_experts) if cfg.n_experts else 1.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(specs)[0]:
        names = [p.key if hasattr(p, "key") else str(p) for p in path]
        size = 1
        for d in leaf.shape:
            size *= d
        n_total += size
        if "embed" in names or names[-1] == "lm_head":
            continue
        if cfg.ffn_kind == "moe" and len(leaf.shape) == 4 \
                and names[-1] in ("w_gate", "w_up", "w_down"):
            n_active += int(size * moe_frac)
        else:
            n_active += size
    return n_active, n_total


def model_flops(bb, shape_kind: str, tokens: int) -> float:
    """6·N_active·tokens (train) or 2·N_active·tokens (serve), plus the
    LM-head matmul term 6/2·tokens·d·V."""
    n_active, _ = active_param_count(bb)
    head = bb.cfg.d_model * bb.plan.eff_vocab(bb.cfg)
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * tokens * (n_active + head)


@dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float          # global useful FLOPs per step
    hlo_flops: float            # per-device compiled FLOPs
    useful_ratio: float         # (model_flops / chips) / hlo_flops
    peaks: ChipPeaks
    n_chips: int = 1

    @property
    def dominant(self) -> str:
        vals = {"compute": self.compute_s, "memory": self.memory_s,
                "collective": self.collective_s}
        return max(vals, key=vals.get)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time over the binding term: time the chip would
        spend on MODEL_FLOPS at peak, divided by the dominant-term time."""
        useful_s = self.model_flops / self.n_chips / self.peaks.flops
        bound = max(self.compute_s, self.memory_s, self.collective_s)
        return useful_s / bound if bound > 0 else 0.0

    def to_json(self) -> Dict[str, Any]:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "hlo_flops": self.hlo_flops,
            "useful_ratio": self.useful_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def derive_terms(cost: Dict[str, float], coll: CollectiveStats,
                 mflops: float, n_chips: int,
                 peaks: ChipPeaks) -> RooflineTerms:
    """cost = compiled.cost_analysis() of the partitioned (per-device) module."""
    hlo_flops = float(cost.get("flops", 0.0))
    hlo_bytes = float(cost.get("bytes accessed", 0.0))
    per_chip_useful = mflops / n_chips
    return RooflineTerms(
        compute_s=hlo_flops / peaks.flops,
        memory_s=hlo_bytes / peaks.hbm_bw,
        collective_s=coll.bytes_total / peaks.ici_bw,
        model_flops=mflops,
        hlo_flops=hlo_flops,
        useful_ratio=(per_chip_useful / hlo_flops) if hlo_flops else 0.0,
        peaks=peaks,
        n_chips=n_chips,
    )


def derive_terms_from_totals(totals, mflops: float, n_chips: int,
                             peaks: ChipPeaks) -> RooflineTerms:
    """Terms from the trip-count-aware HLO cost model (launch.hlocost) —
    the source of record for §Roofline (cost_analysis undercounts loops)."""
    per_chip_useful = mflops / n_chips
    return RooflineTerms(
        compute_s=totals.flops / peaks.flops,
        memory_s=totals.bytes / peaks.hbm_bw,
        collective_s=totals.collective_bytes / peaks.ici_bw,
        model_flops=mflops,
        hlo_flops=totals.flops,
        useful_ratio=(per_chip_useful / totals.flops) if totals.flops else 0.0,
        peaks=peaks,
        n_chips=n_chips,
    )
