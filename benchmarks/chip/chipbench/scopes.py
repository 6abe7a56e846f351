"""Device seconds by the program's named scope (`jax.named_scope`), from
the profiler's trace file and the compiled program's text.

On a TPU the trace names each device op by its HLO instruction
(`%fusion.460 = bf16[...] fusion(...)`) and carries no scope. The scope
an op was traced under is in the compiled program's HLO, as the op's
metadata path (`op_name="jit(decode_step)/while/body/closed_call/mla/..."`).
So an op of the trace that runs inside an execution of `module` (the
"XLA Modules" line) takes the path its instruction has in that module's
text, and counts to the scope of `SCOPES` that is a segment of the path.
XLA's `ragged-dot` kernels carry no path; they count to `moe.experts`,
the only scope whose code calls `lax.ragged_dot`. Seconds are device self
time inside the `bench.window` annotation (`tracing.self_times`),
averaged over the chips.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Optional

from chipbench import tracing

SCOPES = ("mla", "moe.route", "moe.experts")
RAGGED = "ragged-dot"
_OP = re.compile(r'%([^\s=]+) = .*?metadata=\{op_name="([^"]*)"')


def op_paths(hlo_text: str) -> Dict[str, str]:
    """{instruction name: its op_name path} of a compiled HLO module."""
    return {m.group(1): m.group(2) for m in _OP.finditer(hlo_text)}


def instruction(op: str) -> str:
    """`%fusion.460 = bf16[...] fusion(...)` -> `fusion.460`."""
    return op.split(" = ", 1)[0].lstrip("%").strip()


def scope_of(op: str, paths: Dict[str, str]) -> Optional[str]:
    """The scope of `SCOPES` an op of the trace ran under; None when it
    ran under none."""
    name = instruction(op)
    path = paths.get(name)
    if path is not None:
        parts = path.split("/")
        hit = next((s for s in SCOPES if s in parts), None)
        if hit is not None:
            return hit
    return "moe.experts" if name.startswith(RAGGED) else None


def scoped(trace: tracing.Trace, paths: Dict[str, str],
           module: str) -> tracing.Trace:
    """`trace` with each device op renamed to its scope ("" for none); only
    ops inside an execution of `module` can have one."""
    out: tracing.Trace = {}
    for plane, lines in trace.items():
        if not tracing.DEVICE_PLANE.match(plane):
            out[plane] = lines
            continue
        runs = sorted((s, s + d) for n, s, d in
                      lines.get(tracing.MODULES_LINE, [])
                      if tracing.base_name(n) == module)
        starts = [r[0] for r in runs]

        def inside(t: float) -> bool:
            k = bisect.bisect_right(starts, t) - 1
            return k >= 0 and t < runs[k][1]

        out[plane] = {tracing.OPS_LINE: [
            ((scope_of(n, paths) or "") if inside(s) else "", s, d)
            for n, s, d in lines.get(tracing.OPS_LINE, [])]}
    return out


def seconds(trace: tracing.Trace) -> Optional[Dict[str, float]]:
    """{scope: device self seconds in the window} for every scope of
    `SCOPES`, from a trace whose ops are named by scope; None when it has
    no device op or none in a scope."""
    planes = tracing.device_planes(trace)
    if not planes:
        return None
    lo, hi = tracing.window_bounds(trace)
    tot = tracing.op_seconds(trace, lo, hi, planes)
    got = {s: tot.get(s, 0.0) for s in SCOPES}
    return got if any(got.values()) else None


def load(log_dir: str) -> tracing.Trace:
    """The newest `.xplane.pb` under `log_dir` in `tracing`'s neutral form,
    with each device plane's op and module lines (`tracing.load` keeps the
    files; this reads them before it does)."""
    import jax
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out: tracing.Trace = {}
    for plane in data.planes:
        device = bool(tracing.DEVICE_PLANE.match(plane.name))
        keep = (tracing.OPS_LINE, tracing.MODULES_LINE) if device else None
        lines = {}
        for line in plane.lines:
            if keep is not None and line.name not in keep:
                continue
            evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                   for e in line.events
                   if device or e.name.startswith(tracing.SPAN_PREFIX)]
            if evs:
                lines[line.name] = evs
        if lines:
            out[plane.name] = lines
    return out


def scope_seconds(log_dir: Optional[str], hlo_text: Optional[str],
                  module: str = "jit_decode_step"
                  ) -> Optional[Dict[str, float]]:
    """`seconds` of `module`'s ops in the trace under `log_dir`, their
    scopes read from `module`'s compiled text; None without either."""
    if not log_dir or not hlo_text:
        return None
    return seconds(scoped(load(log_dir), op_paths(hlo_text), module))
