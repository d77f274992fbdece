"""Share of the device's busy time in the traced slice spent in the two
Mamba-2 SSD kernels (the Mosaic calls named `ssd_prefill` and
`ssd_decode`, lib/ssd_ops.py): how much of the cell's busy time is the
state-space mechanism the cell was added for.  Over the slice's busy time
(the union of its operations' intervals).  None where the slice holds no
call of either.  Source: device trace."""
from benchmark.lib.ssd_ops import is_ssd_kernel


def compute(ctx):
    took = sum(d for evs in ctx["events"]["devices"].values()
               for name, _, d in evs if is_ssd_kernel(name)) / 1e9
    busy = ctx["trace"]["busy_s"] * len(ctx["events"]["devices"])
    if not took or not busy:
        return None
    return 100.0 * took / busy
