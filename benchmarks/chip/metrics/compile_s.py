"""Mean seconds of XLA's backend compile per fresh ladder point: the
`jax.monitoring` backend-compile duration that the span system adds to
the planner's `planner.compile` span (its `compile_s`), over the
window's spans. A program whose spans carry no such duration reads
nothing."""
from chipbench import ring


def read(bench, outcome):
    spans = ring.window_spans(bench, "planner.compile")
    if not spans or not any("compile_s" in s.attrs for s in spans):
        return None
    return sum(s.attrs.get("compile_s", 0.0) for s in spans) / len(spans)
