"""Share of the window that the thread driving the engine spent in host
work with nothing queued on the device: `serving/host_time` summed over
the six gap phases (lib/host_phases.py), over `timings.window_s`.  What
`device_idle_share` holds beyond this is not that thread's.  Source:
program span."""
from benchmark.lib.host_phases import GAP_PHASES, phase_seconds


def compute(ctx):
    seconds = phase_seconds(ctx["counters"], GAP_PHASES)
    if seconds is None:
        return None
    return 100.0 * seconds / ctx["timings"]["window_s"]
