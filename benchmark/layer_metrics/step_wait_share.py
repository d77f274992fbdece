"""Share of a decode step the engine's thread waits for the device:
`serving/step_wait{phase=decode}` over `serving/step_time{phase=decode}`.
Near 0 the host is the longer side.  Source: program span."""
from benchmark.lib.step_record import wait_share


def compute(ctx):
    return wait_share(ctx["counters"])
