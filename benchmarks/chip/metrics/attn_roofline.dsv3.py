"""The `mla` scope's share of its roofline on DeepSeek-V3's chip share:
the least time the chip needs for each tick's latent attention, the
larger of its FLOPs over the bf16 peak and its bytes over the HBM
bandwidth (`countsmla.attn_flops`, `attn_bytes`: weights as stored, the
latent cache up to each slot's filled positions, the row written), summed
over the window's ticks, over the device self time of the ops traced
under the scope `mla` (`chipbench/scopes.py`). A trace with no op in the
scope reads nothing."""
from chipbench import countsmla


def read(bench, outcome):
    lay = outcome.layer
    secs = (lay.get("scope_s") or {}).get("mla")
    if not secs or not lay.get("tick_slots"):
        return None
    c, pk = lay["config"], bench.peaks
    need = sum(max(countsmla.attn_flops(c, s, kv) / pk.bf16_flops,
                   countsmla.attn_bytes(c, s, kv, lay["param_itemsize"],
                                        lay["kv_itemsize"]) / pk.hbm_bw)
               for s, kv in zip(lay["tick_slots"], lay["tick_kv"]))
    return 100.0 * need / secs
