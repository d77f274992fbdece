"""Host time per program step in `engine/emit`: after the sampler's
read-back, each row's key upload, `record_token` and the latency
histograms.  Source: program span `serving/host_time`."""
from benchmark.lib.host_phases import per_step_ms


def compute(ctx):
    return per_step_ms(ctx["counters"], ("engine/emit",))
