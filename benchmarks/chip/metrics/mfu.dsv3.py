"""The decode step's share of the chip's bf16 peak on DeepSeek-V3's chip
share: the matmul FLOPs the stepped tokens need (`countsmla.step_flops`:
projections, dense MLPs, router, shared expert, the head over the slice,
the absorbed core at each slot's filled positions, and the pairs the
engine's `engine.tick` spans say were routed to held experts), over the
window, over the peak. A program whose ticks carry no `held_routes`
reads nothing."""
from chipbench import countsmla


def read(bench, outcome):
    lay = outcome.layer
    if not lay.get("slot_ticks") or not lay.get("held_routes"):
        return None
    flops = countsmla.step_flops(lay["config"], lay["slot_ticks"],
                                 lay["kv_positions"], sum(lay["held_routes"]))
    return 100.0 * flops / bench.window_s / (
        len(bench.devices) * bench.peaks.bf16_flops)
