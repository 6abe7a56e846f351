"""Mean seconds one fresh ladder point spends before XLA's compile: the
wall of the planner's `planner.lower` span (eval_shape, tracing the step
to a jaxpr and lowering it to StableHLO), over the window's spans, one
per fresh ladder point."""
from chipbench import ring


def read(bench, outcome):
    spans = ring.window_spans(bench, "planner.lower")
    if not spans:
        return None
    return sum(s.wall_s for s in spans) / len(spans)
