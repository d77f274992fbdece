"""What the host needs between two readbacks of a decode step, all of it:
(`serving/step_time{phase=decode}` - `serving/step_wait{phase=decode}`) over
the decode steps - the phases and every microsecond between them.  While it
is under `decode_step_ms.serve` the cell is device-bound, and the distance
is the room a device-side saving has.  Source: program span."""
from benchmark.lib.step_record import host_ms


def compute(ctx):
    return host_ms(ctx["counters"])
