"""Device trace: capture with the JAX profiler, and the reduction from the
trace to numbers.

The reduction works on a neutral form, `{plane: {line: [(name, start_ns,
dur_ns), ...]}}`, so the tests can check it on a small recorded trace
without a chip. Device planes are `/device:TPU:<n>`; their "XLA Ops" line
holds one event per operation that ran, and "XLA Modules" one event per
program execution. Host spans are the benchmark's own annotations, whose
names start with `bench.`; `bench.window` brackets the measured window.
"""
from __future__ import annotations

import bisect
import contextlib
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]            # (name, start_ns, dur_ns)
Trace = Dict[str, Dict[str, List[Event]]]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"^%?(all-reduce|all-gather|reduce-scatter|"
                        r"all-to-all|collective-permute)")


@contextlib.contextmanager
def capture(log_dir: str, enabled: bool = True):
    """Profile the enclosed block into `log_dir` (no-op when disabled).
    Python function tracing is off: only device activity and the
    benchmark's own annotations are recorded."""
    if not enabled:
        yield
        return
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def load(log_dir: str) -> Trace:
    """The newest `.xplane.pb` under `log_dir` in the neutral form, keeping
    device planes whole and only `bench.` spans of the host."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out: Trace = {}
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = {}
        for line in plane.lines:
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events
                   if device or e.name.startswith(SPAN_PREFIX)]
            if evs:
                lines[line.name] = evs
        if lines:
            out[plane.name] = lines
    return out


def device_planes(trace: Trace) -> List[str]:
    return sorted((p for p in trace if DEVICE_PLANE.match(p)),
                  key=lambda p: int(DEVICE_PLANE.match(p).group(1)))


def host_spans(trace: Trace) -> List[Event]:
    spans = []
    for plane, lines in trace.items():
        if DEVICE_PLANE.match(plane):
            continue
        for evs in lines.values():
            spans.extend(e for e in evs if e[0].startswith(SPAN_PREFIX))
    return sorted(spans, key=lambda e: e[1])


def window_bounds(trace: Trace) -> Tuple[float, float]:
    """(start_ns, end_ns) of the `bench.window` span, else of all device
    activity."""
    win = [e for e in host_spans(trace) if e[0] == WINDOW_SPAN]
    if win:
        return win[0][1], win[0][1] + win[0][2]
    evs = [e for p in device_planes(trace)
           for e in trace[p].get(OPS_LINE, [])]
    if not evs:
        raise ValueError("trace has neither a window span nor device ops")
    return min(e[1] for e in evs), max(e[1] + e[2] for e in evs)


def _clip(evs: Iterable[Event], lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for _, s, d in evs:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def covered(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in union(intervals))


def ops(trace: Trace, plane: str) -> List[Event]:
    return trace.get(plane, {}).get(OPS_LINE, [])


def busy_s(trace: Trace, lo: float, hi: float,
           planes: Optional[Sequence[str]] = None) -> float:
    """Seconds in which any operation ran, averaged over the devices."""
    planes = list(planes or device_planes(trace))
    if not planes:
        return 0.0
    return sum(covered(_clip(ops(trace, p), lo, hi)) for p in planes) \
        / len(planes) / 1e9


def self_times(evs: Sequence[Event], lo: float, hi: float) -> List[Tuple[str, float]]:
    """(name, self ns) of each op inside the window. Ops of one line nest
    (a `while` holds its body's ops), so each op's time less that of the
    ops directly inside it is its own; an op that only overlaps the one
    before it takes the overlap from it."""
    out: List[List] = []
    stack: List[int] = []
    for name, s, d in sorted(_within(evs, lo, hi), key=lambda e: (e[1], -e[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[1] -= min(s + d, parent[2]) - s
        out.append([name, d, s + d])
        stack.append(len(out) - 1)
    return [(n, t) for n, t, _ in out]


def _within(evs: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for name, s, d in evs:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def op_seconds(trace: Trace, lo: float, hi: float,
               planes: Optional[Sequence[str]] = None) -> Dict[str, float]:
    """Device self seconds by operation name, summed over the devices and
    divided by their number."""
    planes = list(planes or device_planes(trace))
    tot: Dict[str, float] = {}
    for p in planes:
        for name, t in self_times(ops(trace, p), lo, hi):
            tot[name] = tot.get(name, 0.0) + t / 1e9
    n = max(1, len(planes))
    return {k: v / n for k, v in tot.items()}


def module_runs(trace: Trace, plane: str, lo: float, hi: float) -> List[Event]:
    """Program executions on one device that start inside the window."""
    return [e for e in trace.get(plane, {}).get(MODULES_LINE, [])
            if lo <= e[1] < hi]


def module_seconds(trace: Trace, lo: float, hi: float,
                   plane: Optional[str] = None) -> Dict[str, float]:
    plane = plane or device_planes(trace)[0]
    tot: Dict[str, float] = {}
    for name, _, d in module_runs(trace, plane, lo, hi):
        tot[base_name(name)] = tot.get(base_name(name), 0.0) + d / 1e9
    return tot


def base_name(module: str) -> str:
    """`jit_step(123)` -> `jit_step`: a program's name without its id."""
    return re.sub(r"\(\d+\)$", "", module).strip()


def exposed_collective_s(trace: Trace, lo: float, hi: float,
                         planes: Optional[Sequence[str]] = None) -> float:
    """Seconds of collective operations during which no other operation
    ran on that device, averaged over the devices."""
    planes = list(planes or device_planes(trace))
    if not planes:
        return 0.0
    total = 0.0
    for p in planes:
        evs = ops(trace, p)
        is_coll = [bool(COLLECTIVE.match(e[0])) for e in evs]
        coll = union(_clip([e for e, c in zip(evs, is_coll) if c], lo, hi))
        comp = union(_clip([e for e, c in zip(evs, is_coll) if not c],
                           lo, hi))
        total += sum(b - a for a, b in coll) - _overlap(coll, comp)
    return total / len(planes) / 1e9


def _overlap(a: Sequence[Tuple[float, float]],
             b: Sequence[Tuple[float, float]]) -> float:
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_gaps(trace: Trace, lo: float, hi: float,
              plane: Optional[str] = None) -> List[Tuple[str, float]]:
    """Idle seconds of one device, summed by what the host was doing: the
    shortest `bench.` span (other than the window) covering each gap's
    middle, else "host"."""
    plane = plane or device_planes(trace)[0]
    busy = union(_clip(ops(trace, plane), lo, hi))
    spans = [e for e in host_spans(trace) if e[0] != WINDOW_SPAN]
    gaps = []
    t = lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    starts = [s[1] for s in spans]
    tot: Dict[str, List[float]] = {}
    for a, b in gaps:
        mid = 0.5 * (a + b)
        # spans are sorted by start; nested ones start shortly before
        # their parents end, so a bounded look back finds the innermost
        k = bisect.bisect_right(starts, mid)
        cover = [s for s in spans[max(0, k - 64):k] if mid <= s[1] + s[2]]
        label = min(cover, key=lambda s: s[2])[0] if cover else "host"
        cell = tot.setdefault(label, [0.0, 0])
        cell[0] += (b - a) / 1e9
        cell[1] += 1
    return sorted(((f"{k} (gaps={n})", s) for k, (s, n) in tot.items()),
                  key=lambda kv: -kv[1])


_KIND = re.compile(r" ([a-z][\w\-]*)\(")


def short(op: str) -> str:
    """`%convert.78 = bf16[7,4096]{...} convert(f32[...] ...)` ->
    `convert.78 convert bf16[7,4096]`: an HLO op's name, kind and shape."""
    if " = " not in op:
        return op
    lhs, rhs = op.split(" = ", 1)
    kind = _KIND.search(rhs)
    shape = "" if rhs.startswith("(") else rhs.split("{", 1)[0].split(" ")[0]
    return " ".join(x for x in (lhs.lstrip("%"), kind.group(1) if kind
                                else "", shape) if x)


def top(d: Dict[str, float], n: int = 10) -> List[List]:
    return [[short(k), v]
            for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
