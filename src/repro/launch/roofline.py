"""Roofline term extraction from compiled dry-run artifacts.

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_wire_bytes_per_device / link_bw

`cost_analysis()` reports per-device FLOPs / bytes (XLA SPMD partitions
before costing). Collective bytes are NOT in cost_analysis — we parse the
optimized HLO and charge each op its ring-algorithm wire bytes per device:

    all-gather(out S, group n):      S * (n-1)/n
    reduce-scatter(in S, group n):   S * (n-1)/n
    all-reduce(S, group n):          2 * S * (n-1)/n
    all-to-all(S, group n):          S * (n-1)/n
    collective-permute(S):           S

Hardware peaks per chip come from `PEAKS`, keyed by jax's `device_kind`;
the terms here use the v5e entry, the chip the dry-run targets.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, Optional


@dataclass(frozen=True)
class ChipPeaks:
    flops: float            # dense bf16 FLOP/s
    hbm_bytes: float        # HBM capacity
    hbm_bw: float           # HBM bytes/s
    ici_bw: float           # interconnect bytes/s per link
    source: str


_V5E = ChipPeaks(
    flops=197e12, hbm_bytes=16e9, hbm_bw=819e9,
    ici_bw=50e9,            # 1,600 Gbit/s of chip-to-chip ICI over 4 links
    source='Google Cloud documentation, "TPU v5e"')

# published peaks by `jax.devices()[i].device_kind`: a v5e reports itself
# as "TPU v5 lite", and jax's mesh utilities also know it as "TPU v5e"
PEAKS: Dict[str, ChipPeaks] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of one chip. A kind missing from `PEAKS` is an error, never a
    default: a roofline share against another chip's peaks is wrong."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


V5E = chip_peaks("TPU v5 lite")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\b")
_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|u64|s32|u32|"
                       r"s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, n_devices: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


@dataclass
class CollectiveStats:
    wire_bytes: float = 0.0
    by_kind: Dict[str, float] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)


def parse_collectives(hlo_text: str, n_devices: int) -> CollectiveStats:
    stats = CollectiveStats()
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[-1][:40]:
            continue
        # the op's result type appears right after '= '
        eq = line.find("= ")
        if eq < 0:
            continue
        typ_text = line[eq + 2: line.find("(", eq)]
        size = _shape_bytes(typ_text)
        if size == 0:
            continue
        kind = m.group(1)
        n = max(2, _group_size(line, n_devices))
        ring = (n - 1) / n
        if kind == "all-reduce":
            wire = 2.0 * size * ring
        elif kind == "collective-permute":
            wire = float(size)
        else:
            wire = size * ring
        stats.wire_bytes += wire
        stats.by_kind[kind] = stats.by_kind.get(kind, 0.0) + wire
        stats.counts[kind] = stats.counts.get(kind, 0) + 1
    return stats


@dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_dev: float
    bytes_per_dev: float
    coll_bytes_per_dev: float
    model_flops: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs * n_dev): remat/redundancy waste."""
        if self.flops_per_dev <= 0:
            return 0.0
        return self.model_flops / self.flops_per_dev

    @property
    def mfu_bound(self) -> float:
        """Model-FLOPs utilization at the roofline bound (the score):
        model_flops / (bound_s * peak) per device."""
        if self.bound_s <= 0:
            return 0.0
        return self.model_flops / (self.bound_s * V5E.flops)


def roofline_from(cost: dict, coll: CollectiveStats, n_devices: int,
                  model_flops_total: float) -> Roofline:
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    return Roofline(
        compute_s=flops / V5E.flops,
        memory_s=byts / V5E.hbm_bw,
        collective_s=coll.wire_bytes / V5E.ici_bw,
        flops_per_dev=flops,
        bytes_per_dev=byts,
        coll_bytes_per_dev=coll.wire_bytes,
        model_flops=model_flops_total / max(n_devices, 1),
    )


def roofline_from_hlo(hc, n_devices: int, model_flops_total: float,
                      extra_hbm_bytes: float = 0.0) -> Roofline:
    """Build roofline terms from trip-count-aware HLO costs
    (launch/hlo_costs.py). `extra_hbm_bytes`: analytic non-dot HBM traffic
    per device (optimizer elementwise update: read+write of params/moments/
    master — outside the parsed dot set)."""
    byts = hc.dot_bytes + extra_hbm_bytes
    return Roofline(
        compute_s=hc.dot_flops / V5E.flops,
        memory_s=byts / V5E.hbm_bw,
        collective_s=hc.coll_wire_bytes / V5E.ici_bw,
        flops_per_dev=hc.dot_flops,
        bytes_per_dev=byts,
        coll_bytes_per_dev=hc.coll_wire_bytes,
        model_flops=model_flops_total / max(n_devices, 1),
    )


def model_flops(cfg, shape) -> float:
    """Useful model FLOPs for the whole step across all devices.

    train:    6 * N_active * tokens     (fwd 2 + bwd 4)
    prefill:  2 * N_active * tokens
    decode:   2 * N_active * batch      (one token per sequence)
    (Attention score FLOPs excluded by convention — MODEL_FLOPS = 6·N·D.)
    """
    n = cfg.active_param_count()
    if shape.mode == "train":
        return 6.0 * n * shape.tokens
    if shape.mode == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch
