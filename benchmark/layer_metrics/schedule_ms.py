"""Host time per program step around the scheduler: `engine/schedule`
(deadline sweep, shedding, `scheduler.schedule()`, preemption counts) plus
`engine/retire` (`retire_finished`, request finish, the step's gauges).
Source: program span `serving/host_time`."""
from benchmark.lib.host_phases import per_step_ms


def compute(ctx):
    return per_step_ms(ctx["counters"], ("engine/schedule", "engine/retire"))
