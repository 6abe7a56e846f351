"""Share of the traced window in which no operation ran on the device
(1 - the union of device-op intervals over the window), averaged over the
chips."""
from chipbench import tracing


def read(bench, outcome):
    tr = bench.load_trace()
    if tr is None or not tracing.device_planes(tr):
        return None
    lo, hi = tracing.window_bounds(tr)
    return 100.0 * (1.0 - tracing.busy_s(tr, lo, hi) / ((hi - lo) / 1e9))
