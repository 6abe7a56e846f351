"""Fresh ladder points per decision completed in the window: the
program's `acquisition.fresh` counter over the decisions."""


def read(bench, outcome):
    lay = outcome.layer
    if not lay.get("decisions"):
        return None
    return lay["fresh"] / lay["decisions"]
