"""Share of the traced window in which no operation ran on the device
and no `engine.fetch` span was open: the device idle that the host's own
work between steps causes, where `device_idle.decode` counts all of it.
The engine's fetch spans are placed on the trace's timeline by
`ring.to_trace`; averaged over the chips."""
from chipbench import ring, tracing


def read(bench, outcome):
    tr = bench.load_trace()
    if tr is None or not tracing.device_planes(tr):
        return None
    fetches = ring.window_spans(bench, "engine.fetch")
    to_trace = ring.to_trace(bench, tr)
    if not fetches or to_trace is None:
        return None
    lo, hi = tracing.window_bounds(tr)
    waits = [(to_trace(s.mono_start), to_trace(s.mono_start + s.wall_s))
             for s in fetches]
    planes = tracing.device_planes(tr)
    idle = 0.0
    for p in planes:
        held = [(s, s + d) for _, s, d in tracing.ops(tr, p)] + waits
        idle += (hi - lo) - tracing.covered(
            [(max(a, lo), min(b, hi)) for a, b in held
             if min(b, hi) > max(a, lo)])
    return 100.0 * idle / len(planes) / (hi - lo)
