"""Mean time a request waits in the scheduler, from `add_request` to its
first prefill compute: `serving/queue_wait`, sum over count.  Source:
program counter."""
from benchmark.lib.host_phases import mean_ms


def compute(ctx):
    return mean_ms(ctx["counters"], "serving/queue_wait")
