"""`itl_p95_ms` as the runner computed it, for a cell where that tail is
not held to a bound: the same number, reported as a per-layer metric under
a name of its own.  Source: host clock (the clients')."""


def compute(ctx):
    return ctx["end_to_end"].get("itl_p95_ms")
