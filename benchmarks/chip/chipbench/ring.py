"""The program's own spans (`repro.telemetry`), read from the process's
span ring after a traced window.

A span keeps its start on the monotonic clock (`mono_start`), the clock
of `bench.t_start` and `bench.t_end`; `window_spans` takes every span of
a name, at any depth, that starts inside the window. A ring that has
lost a root the window may hold reads nothing, rather than part of the
window; so does a program whose spans keep no monotonic start.
`to_trace` places such a time on the device trace's timeline.
"""
from __future__ import annotations

from typing import Callable, List, Optional

from chipbench import tracing


def window_spans(bench, name: str) -> Optional[List]:
    """The spans called `name` that start inside the window, by start;
    None when the ring may have lost some."""
    from repro.telemetry.spans import default_ring
    ring = default_ring()
    lost = getattr(ring, "evicted_until", None)
    if lost is not None and lost >= bench.t_start:
        return None
    out, todo = [], list(ring.traces())
    while todo:
        s = todo.pop()
        todo.extend(s.children)
        t = getattr(s, "mono_start", None)
        if s.name == name and t is not None and \
                bench.t_start <= t <= bench.t_end:
            out.append(s)
    return sorted(out, key=lambda s: s.mono_start)


def to_trace(bench, trace: tracing.Trace) -> Optional[Callable[[float], float]]:
    """Monotonic seconds -> the trace's nanoseconds: the line through both
    ends of the `bench.window` annotation against `bench.t_start` and
    `bench.t_end`, which mark the same window on the host's clock. Two
    points, because the two clocks may drift apart over a long window (a
    TPU v5e host's drifted 3 us in 51 s)."""
    win = [e for e in tracing.host_spans(trace) if e[0] == tracing.WINDOW_SPAN]
    if not win or bench.t_end <= bench.t_start:
        return None
    lo, dur = win[0][1], win[0][2]
    scale = dur / (bench.t_end - bench.t_start)
    return lambda t: lo + (t - bench.t_start) * scale
