"""Median, over the requests behind `ttft_p95_ms`, of send -> first
streamed token at the client: the steadier statistic beside the tail.
Source: host clock (the clients')."""


def compute(ctx):
    return ctx["timings"].get("ttft_median_ms")
