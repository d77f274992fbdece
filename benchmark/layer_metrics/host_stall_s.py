"""Seconds of host side in program steps whose host side was over 0.25 s:
`serving/host_stall_seconds`.  0 in a sound run; a run that reads low with
0 here stalled on the device's side or in the clients.  Source: program
counter."""
from benchmark.lib.step_record import stall_seconds


def compute(ctx):
    return stall_seconds(ctx["counters"])
