"""Bytes of retention state a decode step has to move, over the window:
`serving/state_slot_steps{group=retention}` (slots held, summed over decode
steps) x the layers x the PUBLISHED state of a sequence in a layer x 2
(read once, written once; lib/retention_ops.py), over the decode steps.
What K/V bytes a step are to an attention model, and the same at any
context length.  None where the program counts no retention group or the
configuration has no retention state.  Source: program counters."""
from benchmark.lib.retention_ops import (decode_bytes_per_row_layer,
                                         decode_rows, retention_layers)


def compute(ctx):
    c, cfg = ctx["counters"], ctx["config"]
    steps = c.get("serving/step_time{phase=decode}:count", 0)
    rows = decode_rows(c)
    per_row = decode_bytes_per_row_layer(cfg)
    if not steps or not rows or per_row is None:
        return None
    return rows * retention_layers(cfg) * per_row / steps
