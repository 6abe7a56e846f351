"""Nested, thread-aware wall-time spans over `contextvars`, with
distributed-trace identity.

A `span("name", **attrs)` block times itself and attaches to whatever
span is current in this context; the outermost span of a context becomes
a root and is recorded into a bounded in-memory `TraceRing` when it
closes. `contextvars` gives thread isolation for free: each thread (and
each asyncio task, should one ever appear) sees its own current-span
chain, so concurrent pipeline plans never splice into each other's
trees.

Every span carries distributed-tracing identity:

  trace_id    16 hex chars, minted when a trace's first span opens and
              inherited by every descendant — including descendants in
              OTHER processes (the daemon wire protocol forwards it).
  span_id     16 hex chars, unique per span.
  parent_id   the parent's span_id. For a local child this is implied by
              tree position; for a span adopted from a REMOTE parent
              (`span(..., parent={"trace_id": .., "span_id": ..})`) it
              is the only link — `stitch_fleet_traces` in
              repro.telemetry.export grafts such roots back under their
              cross-process parent.

Clock discipline: each trace anchors wall-clock time ONCE — the root
span records an `(epoch, perf_counter)` pair when it opens, and every
descendant derives `started_at = epoch + (perf_counter_now - anchor)`.
Sibling spans therefore can never disagree with their walls after an
NTP step mid-trace: `time.time()` is consulted exactly once per local
trace, all offsets come from the monotonic clock.

    with span("pipeline.plan", signature=sig):
        with span("pipeline.acquire"):
            ...
    for root in default_ring().traces():
        print(root.to_dict())   # {"name", "trace_id", "span_id", ...}

Spans are deliberately tiny (one object, two perf_counter calls, one
contextvar set/reset, one 64-bit id draw) — cheap enough to leave on in
production hot paths; instrumented code that wants a zero-cost off
switch uses `span_if(enabled, ...)`, which degrades to a shared no-op
context manager.

The device trace's timeline: every span also keeps `mono_start`, its
start on the monotonic clock (`perf_counter`, on Linux the
CLOCK_MONOTONIC that `time.monotonic()` reads), so a reader can clip
spans to a window given in monotonic seconds. Once JAX is loaded (this
module never imports it), each span also enters a
`jax.profiler.TraceAnnotation` of its own name — its TWIN on the
profiler's host plane, beside the device's ops in any profiler trace —
and one process-wide `jax.monitoring` listener adds the compile phases
a thread runs to the innermost span open on it (`COMPILE_EVENTS`):
`jaxpr_s` (tracing to a jaxpr), `mlir_s` (lowering to StableHLO),
`compile_s` (XLA's backend compile) and `compiles` (their count), so a
tree says which tick recompiled.

`current_trace_context()` returns the innermost open span's
`{"trace_id", "span_id"}` (or None) — the propagation token clients
stamp onto wire frames (see repro.state.transport.TRACE_FIELD) and
`StructuredLogger` stamps onto log lines.
"""
from __future__ import annotations

import contextvars
import random
import sys
import threading
import time
from collections import deque
from typing import Dict, List, Optional

_current: "contextvars.ContextVar[Optional[Span]]" = \
    contextvars.ContextVar("crispy_current_span", default=None)

# id source: a private urandom-seeded Mersenne instance. getrandbits on
# a shared Random is a single C call (atomic under the GIL) and ~10x
# cheaper than os.urandom per span — collisions at 64 bits are
# negligible for bounded rings of short-lived traces.
_ids = random.Random()

# roots the process ring holds: a 51 s window of 5 ms ticks is 10,200
DEFAULT_RING_CAP = 16384

# the compile phases jax.monitoring reports, and the span attribute
# (seconds, summed) each adds to the innermost span open on its thread
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "mlir_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}


def new_span_id() -> str:
    """A fresh 64-bit hex id (used for both trace and span ids)."""
    return f"{_ids.getrandbits(64):016x}"


class Span:
    """One timed block: identity, name, attributes, children, wall
    seconds. `anchor` is the trace's (epoch, perf_counter) pair and
    `mono_start` the span's own start on the monotonic clock — see the
    module docstring for the clock discipline."""

    __slots__ = ("name", "attrs", "trace_id", "span_id", "parent_id",
                 "started_at", "mono_start", "wall_s", "children",
                 "thread", "anchor")

    def __init__(self, name: str, attrs: Dict):
        self.name = name
        self.attrs = attrs
        self.trace_id: Optional[str] = None
        self.span_id: Optional[str] = None
        self.parent_id: Optional[str] = None
        self.started_at = 0.0
        self.mono_start = 0.0
        self.wall_s = 0.0
        self.children: List[Span] = []
        self.thread = threading.current_thread().name
        self.anchor = None          # (epoch_s, perf_counter_s) of the trace

    def context(self) -> Dict[str, str]:
        """The propagation token for this span: {"trace_id", "span_id"}."""
        return {"trace_id": self.trace_id, "span_id": self.span_id}

    def to_dict(self) -> Dict:
        out = {"name": self.name, "trace_id": self.trace_id,
               "span_id": self.span_id, "started_at": self.started_at,
               "wall_s": self.wall_s, "thread": self.thread}
        if self.parent_id is not None:
            out["parent_id"] = self.parent_id
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.children:
            out["children"] = [c.to_dict() for c in self.children]
        return out

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, trace={self.trace_id}, "
                f"wall_s={self.wall_s:.6f}, "
                f"children={len(self.children)})")


class TraceRing:
    """Bounded ring of finished ROOT spans (children live inside their
    roots). Thread-safe; oldest traces fall off the end. `recorded` is
    the monotonic count of roots ever recorded — ring wrap-around never
    hides throughput from the load benchmarks. `evicted_until` is the
    latest monotonic end of a root that fell off (None while none has):
    a window that opens after it has lost none of its roots."""

    def __init__(self, cap: int = 256):
        self.cap = cap
        self._ring: "deque[Span]" = deque(maxlen=cap)
        self._recorded = 0
        self._evicted_until: Optional[float] = None
        self._lock = threading.Lock()

    def record(self, span_: Span) -> None:
        with self._lock:
            if len(self._ring) == self.cap:
                old = self._ring[0]
                end = old.mono_start + old.wall_s
                if self._evicted_until is None or end > self._evicted_until:
                    self._evicted_until = end
            self._ring.append(span_)
            self._recorded += 1

    def traces(self) -> List[Span]:
        with self._lock:
            return list(self._ring)

    @property
    def recorded(self) -> int:
        with self._lock:
            return self._recorded

    @property
    def evicted_until(self) -> Optional[float]:
        with self._lock:
            return self._evicted_until

    def clear(self) -> None:
        """Empty the ring; it then reads as new (nothing evicted)."""
        with self._lock:
            self._ring.clear()
            self._evicted_until = None

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


_default_ring = TraceRing(DEFAULT_RING_CAP)


def default_ring() -> TraceRing:
    return _default_ring


def current_span() -> Optional[Span]:
    """The innermost open span of this thread/context, or None."""
    return _current.get()


def current_trace_context() -> Optional[Dict[str, str]]:
    """The innermost open span's {"trace_id", "span_id"}, or None —
    what wire clients stamp onto outgoing frames so remote work joins
    this trace."""
    s = _current.get()
    if s is None or s.trace_id is None:
        return None
    return {"trace_id": s.trace_id, "span_id": s.span_id}


# jax.profiler.TraceAnnotation once JAX is loaded and hooked
_annotation = None
_hook_lock = threading.Lock()


def _hook_jax():
    """The twin annotation class, or None while JAX is not loaded. The
    first call after JAX is loaded registers the compile listener — once
    per process."""
    global _annotation
    if "jax" not in sys.modules:
        return None
    with _hook_lock:
        if _annotation is None:
            import jax.monitoring
            import jax.profiler
            jax.monitoring.register_event_duration_secs_listener(
                _on_compile_phase)
            _annotation = jax.profiler.TraceAnnotation
    return _annotation


def _on_compile_phase(event: str, duration: float, **_kw) -> None:
    key = COMPILE_EVENTS.get(event)
    if key is None:
        return
    s = _current.get()
    if s is None:
        return
    attrs = s.attrs
    attrs[key] = attrs.get(key, 0.0) + duration
    if key == "compile_s":
        attrs["compiles"] = attrs.get("compiles", 0) + 1


class _SpanContext:
    """The `span(...)` context manager (a class, not @contextmanager:
    ~2x cheaper to enter and exit, and this sits on hot paths)."""

    __slots__ = ("_span", "_ring", "_parent", "_token", "_t0", "_twin")

    def __init__(self, name: str, ring: Optional[TraceRing],
                 parent: Optional[Dict], attrs: Dict):
        self._span = Span(name, attrs)
        self._ring = ring
        self._parent = parent

    def __enter__(self) -> Span:
        s = self._span
        local_parent = _current.get()
        t0 = time.perf_counter()
        if local_parent is not None and local_parent.anchor is not None:
            # inherit the trace: identity AND its one clock anchor
            s.trace_id = local_parent.trace_id
            s.parent_id = local_parent.span_id
            s.anchor = local_parent.anchor
        else:
            remote = self._parent
            if remote:
                # adopted from another process/thread: same trace id,
                # remote span as parent — but a FRESH local clock anchor
                # (the remote one lives on a different host clock)
                s.trace_id = remote.get("trace_id") or new_span_id()
                s.parent_id = remote.get("span_id")
            else:
                s.trace_id = new_span_id()
            s.anchor = (time.time(), t0)
        s.span_id = new_span_id()
        s.started_at = s.anchor[0] + (t0 - s.anchor[1])
        s.mono_start = t0
        self._token = _current.set(s)
        self._t0 = t0
        twin = _annotation or _hook_jax()
        if twin is not None:
            twin = twin(s.name)
            twin.__enter__()
        self._twin = twin
        return s

    def __exit__(self, *exc) -> None:
        s = self._span
        if self._twin is not None:
            self._twin.__exit__(None, None, None)
        s.wall_s = time.perf_counter() - self._t0
        _current.reset(self._token)
        parent = _current.get()
        if parent is not None:
            parent.children.append(s)
        else:
            (self._ring if self._ring is not None
             else _default_ring).record(s)


def span(name: str, ring: Optional[TraceRing] = None,
         parent: Optional[Dict] = None, **attrs) -> _SpanContext:
    """Open a timed span; nested calls build a tree, the outermost lands
    in `ring` (default: the process ring) when it exits. `parent` is an
    optional REMOTE trace context ({"trace_id", "span_id"}, e.g. taken
    off a wire frame): the span joins that trace as a cross-process
    child — ignored when a local parent span is already open."""
    return _SpanContext(name, ring, parent, attrs)


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        pass


_NULL_SPAN = _NullSpan()


def span_if(enabled: bool, name: str, ring: Optional[TraceRing] = None,
            parent: Optional[Dict] = None, **attrs):
    """`span(...)` when `enabled`, else a shared no-op context manager —
    the branch instrumented hot paths use so a disabled registry costs
    one attribute load."""
    if not enabled:
        return _NULL_SPAN
    return _SpanContext(name, ring, parent, attrs)
