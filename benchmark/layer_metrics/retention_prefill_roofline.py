"""The retention prefill kernel's share of its roofline, over the traced
slice alone: for every call of the kernel in it, the larger of the time
the chip's MXU needs for the call's positions (`2 x 8256 x 129 x (40 + 8)`
FLOP a position through the state plus the products inside a chunk,
lib/retention_ops.py) and the time its HBM needs to read and write the
row's state once, summed, over the calls' summed time.  The positions of
a call are read from its result's shape in the trace.  Operations and
bytes are of the PUBLISHED state, so padding shows as lost share.  None
where the configuration has no retention state or the slice holds no call
of the kernel.  Source: device trace."""
from benchmark.lib.retention_ops import (decode_bytes_per_row_layer,
                                         prefill_calls,
                                         prefill_flops_per_token_layer)


def compute(ctx):
    cfg, peaks = ctx["config"], ctx["peaks"]
    flops = prefill_flops_per_token_layer(cfg)
    if flops is None:
        return None
    calls = prefill_calls(ctx["events"], cfg)
    took = sum(s for _, s in calls)
    if not took:
        return None
    state = decode_bytes_per_row_layer(cfg) / peaks["hbm_bytes_per_s"]
    least = sum(max(n * flops / peaks["bf16_flops_per_s"], state)
                for n, _ in calls)
    return 100.0 * least / took
