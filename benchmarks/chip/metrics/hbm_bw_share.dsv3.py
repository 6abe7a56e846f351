"""The decode step's share of the chip's HBM bandwidth on DeepSeek-V3's
chip share: the bytes each tick's step must read and write
(`countsmla.step_bytes`: every weight as stored but the held experts no
pair reached, by the tick's `experts_hit`, and the latent cache up to
each slot's filled positions), over the step program's device time in
the trace, over the bandwidth. The step is the program that took the
most device time in the window; the bytes are counted for the ticks
whose step the trace holds, at the window's mean a tick. A program
whose ticks carry no routing attributes reads nothing."""
from chipbench import countsmla, tracing


def read(bench, outcome):
    lay = outcome.layer
    tr = bench.load_trace()
    if tr is None or not lay.get("experts_hit"):
        return None
    lo, hi = tracing.window_bounds(tr)
    plane = tracing.device_planes(tr)[0]
    secs = tracing.module_seconds(tr, lo, hi, plane)
    if not secs:
        return None
    step = max(secs, key=secs.get)
    runs = sum(1 for e in tracing.module_runs(tr, plane, lo, hi)
               if tracing.base_name(e[0]) == step)
    c = lay["config"]
    must = sum(countsmla.step_bytes(c, s, kv, hit, lay["param_itemsize"],
                                    lay["kv_itemsize"])
               for s, kv, hit in zip(lay["tick_slots"], lay["tick_kv"],
                                     lay["experts_hit"]))
    return 100.0 * must / len(lay["experts_hit"]) * runs / secs[step] / \
        bench.peaks.hbm_bw
