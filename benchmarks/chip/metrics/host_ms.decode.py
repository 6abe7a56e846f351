"""Mean milliseconds of the host's own time in one decode tick: the
engine's `engine.tick` span less its `engine.fetch` child, in which the
host waits for the step's logits. Over the ticks that start in the
window."""
from chipbench import ring


def read(bench, outcome):
    ticks = ring.window_spans(bench, "engine.tick")
    if not ticks:
        return None
    own = [t.wall_s - sum(c.wall_s for c in t.children
                          if c.name == "engine.fetch") for t in ticks]
    return 1e3 * sum(own) / len(own)
