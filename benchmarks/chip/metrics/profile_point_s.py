"""Mean seconds of one fresh ladder point: the program's
`acquisition.profile_seconds` histogram, its sum over its count for the
points profiled inside the window."""


def read(bench, outcome):
    lay = outcome.layer
    if not lay.get("profile_count"):
        return None
    return lay["profile_sum"] / lay["profile_count"]
