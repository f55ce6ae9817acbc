"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of
HBM at 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (4 links of
50 GB/s). A kind that is not here is an error, never a default.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class Peaks:
    flops: float      # dense bf16 FLOP/s
    hbm_bw: float     # HBM bytes/s
    ici_bw: float     # inter-chip bytes/s per link


PEAKS: Dict[str, Peaks] = {
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(PEAKS)}") from None
