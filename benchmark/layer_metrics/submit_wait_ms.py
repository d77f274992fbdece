"""Mean time a request waits in the API's submit queue, from the HTTP
handler's `put` to the pump's `add_request` (the pump is inside a step
meanwhile): `serving/submit_wait`, sum over count.  Source: program
counter."""
from benchmark.lib.host_phases import mean_ms


def compute(ctx):
    return mean_ms(ctx["counters"], "serving/submit_wait")
