"""Mean host-clock milliseconds of one `ServeEngine.tick()` in the window:
admission, the model step, the copy of the logits to the host and
sampling. Read from the benchmark's own span around each tick."""


def read(bench, outcome):
    ticks = outcome.layer.get("tick_s")
    if not ticks:
        return None
    return 1e3 * sum(ticks) / len(ticks)
