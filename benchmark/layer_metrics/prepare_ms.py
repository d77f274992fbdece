"""Host time per program step in `engine/prepare`: building the step's
input arrays, their uploads, the model program's dispatch call and
storing the returned KV handles.  Source: program span
`serving/host_time`."""
from benchmark.lib.host_phases import per_step_ms


def compute(ctx):
    return per_step_ms(ctx["counters"], ("engine/prepare",))
