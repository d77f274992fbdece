"""The SSD prefill kernel's share of its roofline, over the traced slice
alone: for every call of the kernel in it, the larger of the time the
chip's MXU needs for the call's positions (5,373,952 FLOP a position at the
published widths in chunks of 128, lib/ssd_ops.py) and the time its HBM
needs to write each row's state once, summed, over the calls' summed time.
The rows and positions of a call are read from its result's shape in the
trace.  None where the configuration has no Mamba-2 state or the slice
holds no call of the kernel.  Source: device trace."""
from benchmark.lib.ssd_ops import (prefill_calls,
                                   prefill_flops_per_token_layer, state_bytes)


def compute(ctx):
    cfg, peaks = ctx["config"], ctx["peaks"]
    flops = prefill_flops_per_token_layer(cfg)
    if flops is None:
        return None
    calls = prefill_calls(ctx["events"])
    took = sum(s for _, _, s in calls)
    if not took:
        return None
    state = state_bytes(cfg) / peaks["hbm_bytes_per_s"]
    least = sum(max(n * flops / peaks["bf16_flops_per_s"], rows * state)
                for rows, n, _ in calls)
    return 100.0 * least / took
