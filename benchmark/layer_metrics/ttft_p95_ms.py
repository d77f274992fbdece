"""`ttft_p95_ms` as the runner computed it, for a cell whose window holds
too few requests for that tail to be held to a bound: the same number,
reported as a per-layer metric under a name of its own.  Source: host
clock (the clients')."""


def compute(ctx):
    return ctx["end_to_end"].get("ttft_p95_ms")
