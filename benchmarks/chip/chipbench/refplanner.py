"""Plain reference of an allocation decision from the ladder points a
decision measured: a least-squares line of bytes against depth, trusted
only when it explains more than 99% of the variance (Crispy's gate),
extrapolated to the full depth with no leeway, and the cheapest slice of
the catalog whose chips, less the per-chip overhead, hold it; and the
floors the measured ladder points have to clear, counted from the
configuration file. Imports nothing of the program; `dtype` float32 is
the control."""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench.counts import dense_layer_params

GiB = 1024 ** 3
R2_GATE = 0.99


def decide(points: Sequence[Tuple[float, float]],
           catalog: List[Tuple[str, int, float, float]], overhead_gib: float,
           full_size: float, dtype=np.float64) -> Dict:
    x = np.asarray([p[0] for p in points], dtype)
    y = np.asarray([p[1] for p in points], dtype)
    A = np.stack([x, np.ones_like(x)], axis=1)
    (slope, icpt), *_ = np.linalg.lstsq(A, y, rcond=None)
    res = y - (slope * x + icpt)
    ss_tot = np.sum(np.square(y - y.mean()))
    r2 = 1.0 - float(np.sum(np.square(res)) / ss_tot) if ss_tot else 0.0
    req = 0.0
    if len(points) >= 2 and r2 > R2_GATE:
        req = float(max(dtype(0), slope * dtype(full_size) + icpt)
                    / dtype(GiB))
    fits = [r for r in catalog if (r[2] - overhead_gib) * r[1] >= req]
    if not fits:
        fits = [max(catalog, key=lambda r: ((r[2] - overhead_gib) * r[1],
                                            -r[3]))]
    best = min(fits, key=lambda r: r[3])
    return {"requirement_gib": req, "config": best[0], "r2": r2}


def gap(wire: Dict, ref: Dict) -> float:
    """1 when the slices differ, else the requirement's relative gap (over
    one byte where the reference's requirement is smaller)."""
    if wire["config"] != ref["config"]:
        return 1.0
    return abs(wire["requirement_gib"] - ref["requirement_gib"]) / max(
        ref["requirement_gib"], 1.0 / GiB)


def profile_floor(c: Dict, seq: int, batch: int,
                  stored: Dict) -> Tuple[int, int]:
    """(bytes every depth holds, bytes each layer adds) at the least for a
    decode step over `batch` rows of `seq` cached positions: the parameters
    as the configuration stores them, and one copy of each layer's keys and
    values. `stored` gives the bytes of a parameter and of a cached value."""
    d, V = c["d_model"], c["vocab_size"]
    per_token = 2 * c["n_kv_heads"] * (d // c["n_heads"]) * stored["kv"]
    layer = (dense_layer_params(c) + 2 * d) * stored["param"] + \
        seq * batch * per_token
    return (2 * V * d + d) * stored["param"], layer


def _ratio(floor: float, measured: float) -> float:
    return floor / measured if measured > 0 else float("inf")


def ladder_floors(decisions: Sequence[Tuple[int, int, Sequence]], c: Dict,
                  stored: Dict) -> Dict[str, Optional[float]]:
    """The ladder points of `decisions`, [(seq, batch, [(depth, bytes)])],
    against the floors. Each number is over 1 where the program holds less
    than the step must:

    bytes_floor  the floor at a point over the bytes measured there, at the
                 worst point;
    slope_floor  a layer's floor over the least-squares bytes per layer of
                 the decision's points, at the worst decision;
    cache_floor  the floor's bytes per cached token and layer over the
                 growth of the per-layer slope with seq x batch across the
                 decisions; None with fewer than two cache sizes.
    """
    bytes_floor, slope_floor, slopes = 0.0, 0.0, []
    for seq, batch, pts in decisions:
        fixed, layer = profile_floor(c, seq, batch, stored)
        depth = np.asarray([p[0] for p in pts], np.float64)
        got = np.asarray([p[1] for p in pts], np.float64)
        bytes_floor = max([bytes_floor] + [_ratio(fixed + layer * x, y)
                                           for x, y in zip(depth, got)])
        slope = float(np.polyfit(depth, got, 1)[0])
        slope_floor = max(slope_floor, _ratio(layer, slope))
        slopes.append((seq * batch, slope))
    cache_floor = None
    if len({t for t, _ in slopes}) >= 2:
        growth = float(np.polyfit([t for t, _ in slopes],
                                  [s for _, s in slopes], 1)[0])
        per_token = 2 * c["n_kv_heads"] * (c["d_model"] // c["n_heads"]) * \
            stored["kv"]
        cache_floor = _ratio(per_token, growth)
    return {"bytes_floor": bytes_floor, "slope_floor": slope_floor,
            "cache_floor": cache_floor}
