"""Milliseconds per decision spent fitting the memory model,
extrapolating it and selecting a slice: the pipeline's own stage walls
(`fit`, `extrapolate`, `select`) of each decision in the window, which
the endpoint returns with `include_trace`. The stage histograms are not
read, because the service samples the warm stages' histograms 1 in 8."""


def read(bench, outcome):
    walls = outcome.layer.get("fit_select_s")
    if not walls:
        return None
    return 1e3 * sum(walls) / len(walls)
