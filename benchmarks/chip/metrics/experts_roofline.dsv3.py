"""The expert layer's share of its roofline on DeepSeek-V3's chip share:
the least time the chip needs for each tick's router, shared expert and
held experts, the larger of their FLOPs over the bf16 peak (the router
and the shared expert for every stepped token, the held experts for the
`held_routes` pairs of the tick's `engine.tick` span) and their bytes
over the HBM bandwidth (router, bias and shared expert as stored, and the
`experts_hit` held experts the tick reached), summed over the window's
ticks, over the device self time of the ops traced under `moe.route` and
`moe.experts` (`chipbench/scopes.py`). A program whose ticks carry no
routing attributes, or a trace with no op in the scopes, reads nothing."""
from chipbench import countsmla


def read(bench, outcome):
    lay = outcome.layer
    scope_s = lay.get("scope_s") or {}
    secs = scope_s.get("moe.route", 0.0) + scope_s.get("moe.experts", 0.0)
    if not secs or not lay.get("held_routes"):
        return None
    c, pk, item = lay["config"], bench.peaks, lay["param_itemsize"]
    need = sum(max(countsmla.experts_flops(c, s, held) / pk.bf16_flops,
                   countsmla.experts_bytes(c, hit, item) / pk.hbm_bw)
               for s, held, hit in zip(lay["tick_slots"], lay["held_routes"],
                                       lay["experts_hit"]))
    return 100.0 * need / secs
