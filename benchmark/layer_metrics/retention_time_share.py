"""Share of the device's operation time in the traced slice spent in the
two retention kernels (the Mosaic calls named `retention_prefill` and
`retention_decode`, lib/retention_ops.py): how much of the cell's busy
time is the mechanism the cell was added for.  None where the slice holds
no call of either.  Source: device trace."""
from benchmark.lib.retention_ops import is_retention_kernel
from benchmark.lib.trace import share_of


def compute(ctx):
    events = ctx["events"]
    if not any(is_retention_kernel(name)
               for evs in events["devices"].values() for name, _, _ in evs):
        return None
    return 100.0 * share_of(events, is_retention_kernel)
