"""Host time per program step that the API's pump spends outside
`engine.step()`: `api/drain_submits` (with its blocking `get`) plus
`api/push_progress` (a `queue.put` per stream).  Source: program span
`serving/host_time`."""
from benchmark.lib.host_phases import per_step_ms


def compute(ctx):
    return per_step_ms(ctx["counters"],
                       ("api/drain_submits", "api/push_progress"))
