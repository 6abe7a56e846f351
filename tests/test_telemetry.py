"""Telemetry plane (repro.telemetry): concurrent counter/histogram
exactness, span nesting + thread isolation, exporter round-trips, fleet
snapshots over a live daemon from a second process, the daemon `metrics`
op on both transports, and the <5% warm-start overhead regression pin."""
import json
import os
import socket
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from repro.allocator.registry import ModelRegistry
from repro.core.catalog import aws_like_catalog
from repro.core.simulator import (GiB, build_history, make_profile_fn,
                                  scout_like_jobs)
from repro.pipeline import AllocationPipeline, PipelineRequest
from repro.state import CrispyDaemon, DaemonBackend, InMemoryBackend
from repro.telemetry import (MetricsRegistry, StructuredLogger, TraceRing,
                             aggregate_fleet, current_span, fleet_snapshot,
                             publish_snapshot, render_prometheus, span,
                             span_if)

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "src")
needs_unix_sockets = pytest.mark.skipif(
    not hasattr(socket, "AF_UNIX"),
    reason="unix-domain sockets unavailable")


def _daemon_socket() -> str:
    # AF_UNIX paths are length-limited (~108 bytes); use a short tempdir
    d = tempfile.mkdtemp(prefix="crispyt-")
    return os.path.join(d, "d.sock")


# -- metrics: concurrent exactness --------------------------------------------


def test_counter_and_histogram_exact_under_8_threads():
    """Per-thread shards must lose nothing: 8 threads x 5000 increments
    and observations fold to exact totals."""
    reg = MetricsRegistry()
    c = reg.counter("hammer.count")
    h = reg.histogram("hammer.seconds")
    per_thread, threads = 5000, 8
    barrier = threading.Barrier(threads)

    def work(tid):
        barrier.wait()                 # maximize interleaving
        for i in range(per_thread):
            c.inc()
            h.observe((tid + 1) * 1e-5)

    ts = [threading.Thread(target=work, args=(tid,))
          for tid in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()

    assert c.value == threads * per_thread
    s = h.summary()
    assert s["count"] == threads * per_thread
    assert s["min"] == pytest.approx(1e-5)
    assert s["max"] == pytest.approx(8e-5)
    assert s["sum"] == pytest.approx(
        sum((tid + 1) * 1e-5 for tid in range(threads)) * per_thread)
    assert sum(s["buckets"]) == s["count"]
    assert 0 < s["p50"] <= s["p99"] <= s["max"]


def test_registry_caches_instruments_and_rejects_kind_conflicts():
    reg = MetricsRegistry()
    assert reg.counter("a.b") is reg.counter("a.b")
    assert reg.histogram("a.c") is reg.histogram("a.c")
    with pytest.raises(ValueError):
        reg.histogram("a.b")           # already a counter
    with pytest.raises(ValueError):
        reg.gauge("a.c")               # already a histogram


def test_disabled_registry_is_inert():
    reg = MetricsRegistry(enabled=False)
    c, h, g = reg.counter("x"), reg.histogram("y"), reg.gauge("z")
    c.inc()
    h.observe(1.0)
    g.set(3.0)
    assert c.value == 0.0 and h.count == 0 and g.value == 0.0
    snap = reg.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}


# -- spans: nesting + thread isolation ----------------------------------------


def test_span_nesting_builds_tree_in_private_ring():
    ring = TraceRing()
    with span("root", ring=ring, job="j1") as root:
        with span("child-a", ring=ring):
            with span("grandchild", ring=ring):
                pass
        with span("child-b", ring=ring):
            pass
    assert current_span() is None
    traces = ring.traces()
    assert [t.name for t in traces] == ["root"]
    assert root.attrs == {"job": "j1"}
    assert [c.name for c in root.children] == ["child-a", "child-b"]
    assert [g.name for g in root.children[0].children] == ["grandchild"]
    assert root.wall_s >= root.children[0].wall_s >= \
        root.children[0].children[0].wall_s >= 0.0
    d = root.to_dict()
    assert d["children"][0]["children"][0]["name"] == "grandchild"
    json.dumps(d)                      # export-safe


def test_spans_are_thread_isolated():
    """contextvars keep each thread's current-span chain private: two
    threads nesting concurrently never splice into each other's trees."""
    ring = TraceRing()
    barrier = threading.Barrier(4)

    def work(tid):
        with span(f"root-{tid}", ring=ring):
            barrier.wait()             # all four roots open at once
            with span(f"inner-{tid}", ring=ring):
                assert current_span().name == f"inner-{tid}"
        assert current_span() is None

    ts = [threading.Thread(target=work, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()

    roots = {t.name: t for t in ring.traces()}
    assert set(roots) == {f"root-{i}" for i in range(4)}
    for i in range(4):
        r = roots[f"root-{i}"]
        assert [c.name for c in r.children] == [f"inner-{i}"]


def test_span_if_disabled_is_noop():
    ring = TraceRing()
    with span_if(False, "nope", ring=ring) as s:
        assert s is None and current_span() is None
    assert len(ring) == 0


# -- exporters ----------------------------------------------------------------


def _sample_registry() -> MetricsRegistry:
    reg = MetricsRegistry()
    reg.counter("req.total").inc(7)
    reg.gauge("queue.depth").set(3)
    h = reg.histogram("req.seconds")
    for v in (0.001, 0.002, 0.004, 0.1):
        h.observe(v)
    return reg


def test_render_prometheus_exposition():
    text = render_prometheus(_sample_registry())
    lines = text.splitlines()
    assert "crispy_req_total_total 7" in lines
    assert "crispy_queue_depth 3" in lines
    assert "# TYPE crispy_req_seconds histogram" in lines
    assert "crispy_req_seconds_count 4" in lines
    # cumulative buckets: the +Inf series equals the count
    assert 'crispy_req_seconds_bucket{le="+Inf"} 4' in lines
    # every metric name survives the sanitizer (alnum + underscore only)
    for ln in lines:
        if not ln.startswith("#"):
            name = ln.split("{")[0].split(" ")[0]
            assert name.replace("_", "").isalnum(), ln


def test_fleet_publish_and_aggregate_in_memory():
    backend = InMemoryBackend()
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("req.total").inc(3)
    b.counter("req.total").inc(4)
    a.histogram("req.seconds").observe(0.001)
    b.histogram("req.seconds").observe(0.1)
    publish_snapshot(backend, "svc-a", a)
    publish_snapshot(backend, "svc-b", b)
    publish_snapshot(backend, "svc-a", a)      # later row wins per source

    fleet = fleet_snapshot(backend)
    assert set(fleet) == {"svc-a", "svc-b"}
    agg = aggregate_fleet(fleet)
    assert agg["sources"] == ["svc-a", "svc-b"]
    assert agg["counters"]["req.total"] == 7
    h = agg["histograms"]["req.seconds"]
    assert h["count"] == 2
    assert h["sum"] == pytest.approx(0.101)
    assert h["min"] == pytest.approx(0.001)
    assert h["max"] == pytest.approx(0.1)
    assert h["p50"] <= h["p99"] <= h["max"]


# -- structured logging -------------------------------------------------------


def test_structured_logger_emits_parseable_lines_and_levels():
    import io
    buf = io.StringIO()
    log = StructuredLogger("unit", stream=buf, level="info")
    log.debug("dropped")               # below threshold
    log.info("served", n=3, addr="unix:/tmp/x")
    log.error("boom", error=ValueError("nope"))    # stringified, not raised
    lines = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    assert [r["event"] for r in lines] == ["served", "boom"]
    assert lines[0]["component"] == "unit" and lines[0]["n"] == 3
    assert lines[1]["level"] == "error" and "nope" in lines[1]["error"]


# -- daemon: metrics op on both transports + cross-process fleet --------------


@needs_unix_sockets
def test_daemon_metrics_op_over_unix_and_tcp():
    sock = _daemon_socket()
    with CrispyDaemon(sock, listen="127.0.0.1:0") as d:
        for target in (sock, d.tcp_address):
            be = DaemonBackend(target)
            try:
                be.append("ns", {"x": 1})
                be.metrics()
                # an op's own wall is observed AFTER its response is
                # built, so daemon.op.metrics.seconds shows up from the
                # second metrics call on
                m = be.metrics()
                assert m["counters"]["daemon.frames"] >= 3
                assert m["counters"]["daemon.bytes_in"] > 0
                assert "daemon.op.append.seconds" in m["histograms"]
                assert "daemon.op.metrics.seconds" in m["histograms"]
                assert m["histograms"]["daemon.op.append.seconds"][
                    "count"] >= 1
            finally:
                be.close()


_PUBLISHER = """
import sys
sys.path.insert(0, {src!r})
from repro.state import DaemonBackend
from repro.telemetry import MetricsRegistry, publish_snapshot
backend = DaemonBackend(sys.argv[1])
reg = MetricsRegistry()
reg.counter("child.requests").inc(11)
reg.histogram("child.seconds").observe(0.002)
publish_snapshot(backend, "svc-child", reg)
backend.close()
print("published")
"""


@needs_unix_sockets
def test_fleet_snapshot_spans_processes_via_daemon():
    """A second real process publishes its snapshot through the daemon;
    this process sees it next to its own in one fleet view."""
    sock = _daemon_socket()
    with CrispyDaemon(sock):
        proc = subprocess.run(
            [sys.executable, "-c", _PUBLISHER.format(src=SRC), sock],
            capture_output=True, text=True, timeout=60)
        assert proc.returncode == 0, proc.stderr[-3000:]
        assert "published" in proc.stdout

        mine = MetricsRegistry()
        mine.counter("parent.requests").inc(5)
        be = DaemonBackend(sock)
        try:
            publish_snapshot(be, "svc-parent", mine)
            fleet = fleet_snapshot(be)
        finally:
            be.close()

    assert set(fleet) == {"svc-child", "svc-parent"}
    agg = aggregate_fleet(fleet)
    assert agg["counters"]["child.requests"] == 11
    assert agg["counters"]["parent.requests"] == 5
    assert agg["histograms"]["child.seconds"]["count"] == 1


# -- the overhead pin ---------------------------------------------------------


def _warm_pipeline(enabled: bool):
    corpus = scout_like_jobs()
    job = next(j for j in corpus if j.mem_profile == "linear")
    catalog = aws_like_catalog()
    history = build_history(corpus, catalog)
    pipe = AllocationPipeline(catalog, history, registry=ModelRegistry(),
                              telemetry=MetricsRegistry(enabled=enabled))
    req = PipelineRequest(job.name, make_profile_fn(job),
                         job.dataset_gib * GiB)
    pipe.run(req)                              # register a confident model
    assert pipe.warm_start(job.name) is not None
    return pipe, req


def test_warm_start_overhead_within_5_percent():
    """Acceptance pin: a warm-start plan with telemetry ENABLED stays
    within 5% of a no-op'd registry. Measured as min-of-interleaved-
    rounds (the min estimator converges on the true floor and is robust
    to scheduler noise); rounds keep adding until the pin holds or the
    round budget runs out, since extra rounds can only sharpen both
    floors, never fake a pass."""
    pe, re_ = _warm_pipeline(enabled=True)
    pd, rd = _warm_pipeline(enabled=False)
    n = 400

    def round_(pipe, req):
        t0 = time.perf_counter()
        for _ in range(n):
            pipe.run(req)
        return (time.perf_counter() - t0) / n

    on = off = float("inf")
    for i in range(24):
        on = min(on, round_(pe, re_))
        off = min(off, round_(pd, rd))
        if i >= 5 and on <= off * 1.05:
            break
    assert on <= off * 1.05, (
        f"telemetry overhead {((on / off) - 1) * 100:.2f}% on the warm "
        f"path (enabled {on * 1e6:.2f}us vs disabled {off * 1e6:.2f}us) "
        f"exceeds the 5% pin")
    # and the enabled run actually recorded: exact warm-hit counters
    snap = pe.telemetry.snapshot()
    assert snap["counters"]["pipeline.warm_start.hits"] > 0
