"""The retention decode kernel's share of its roofline: per decode step
and layer, the time the chip's HBM needs to read and write the PUBLISHED
states of the step's live rows (`serving/state_slot_steps{group=retention}`
over the window's decode steps x 2 x 34.08 MB, lib/retention_ops.py), over
the kernel's mean call time in the traced slice (one call a layer a step).
At ~1.5 FLOP a byte the bytes set it.  The kernel moves the pool's padded
state (+6.3%) and every row of the fixed-shape batch, live or not, so the
share cannot pass 100%.  None where the program counts no retention group,
the configuration has no retention state or the slice holds no call of the
kernel.  Source: device trace."""
from benchmark.lib.retention_ops import (DECODE_KERNEL,
                                         decode_bytes_per_row_layer,
                                         decode_rows, kernel_call_seconds)


def compute(ctx):
    c, cfg = ctx["counters"], ctx["config"]
    steps = c.get("serving/step_time{phase=decode}:count", 0)
    rows = decode_rows(c)
    per_row = decode_bytes_per_row_layer(cfg)
    if not steps or not rows or per_row is None:
        return None
    seconds, calls = kernel_call_seconds(ctx["events"], DECODE_KERNEL)
    if not calls:
        return None
    least = rows / steps * per_row / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (seconds / calls)
