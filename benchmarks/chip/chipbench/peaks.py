"""Published peaks of the chips the benchmark runs on, keyed by the
`device_kind` that JAX reports. A kind that is not in the table is an
error: a share of another chip's peak would be wrong, so there is no
default and no CPU entry."""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float       # dense bf16 FLOP/s per chip
    hbm_bw: float           # HBM bytes/s per chip
    hbm_bytes: float        # HBM capacity per chip
    ici_bw: float           # chip-to-chip bytes/s per chip
    source: str


_V5E = Peaks(bf16_flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
             ici_bw=1600e9 / 8,
             source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                    'bf16, 16 GB HBM at 819 GB/s, 1,600 Gbit/s ICI')

# a v5e reports itself as "TPU v5 lite"; some JAX utilities call it "TPU v5e"
PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None
