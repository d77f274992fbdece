"""How much of its busy time the engine's thread ran: `serving/host_cpu`
over `serving/host_time`, both summed over the six phases that should never
wait (lib/step_record.BUSY_PHASES).  The rest it lost to the GIL, the
scheduler or the runtime.  Source: program counter."""
from benchmark.lib.step_record import cpu_share


def compute(ctx):
    return cpu_share(ctx["counters"])
