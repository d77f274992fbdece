"""Median, pooled over the requests behind `itl_p95_ms`, of the gap
between consecutive streamed tokens at the client: a decode step with no
foreign prefill in it.  Source: host clock (the clients')."""


def compute(ctx):
    return ctx["timings"].get("itl_median_ms")
