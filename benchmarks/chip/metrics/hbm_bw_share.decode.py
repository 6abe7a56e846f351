"""The decode step's share of the chip's HBM bandwidth: the bytes the
step must read (parameters as stored, the KV cache up to the filled
positions) over the step program's device time in the trace. The step is
the program that took the most device time in the window; the bytes are
counted for the ticks whose step the trace holds."""
from chipbench import counts, tracing


def read(bench, outcome):
    lay = outcome.layer
    tr = bench.load_trace()
    if tr is None or not lay.get("ticks"):
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
    must = lay["ticks"] * counts.decode_step_bytes(
        c, lay["param_itemsize"], lay["kv_itemsize"], 0) + \
        counts.decode_step_bytes(c, 0, lay["kv_itemsize"],
                                 lay["kv_positions"])
    return 100.0 * must * runs / lay["ticks"] / secs[step] / \
        bench.peaks.hbm_bw
