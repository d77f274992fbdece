"""The ragged decode kernel's share of its memory roofline: the bytes of
K/V a decode step has to read (lib/afmoe_ops.decode_kv_bytes over the
window's decode steps: live keys x layers x bytes a token a layer) over the
chip's HBM rate, over the kernel's time a step (its mean call time in the
traced slice x the model's layers).  The bytes are a lower bound (the
kernel moves whole blocks, and its own block writes are not counted), so
the share cannot pass 100%.  Bound by memory: at one query row a sequence
the products are too small to count.  Source: device trace."""
from benchmark.lib.afmoe_ops import (RAGGED_KERNEL, decode_kv_bytes,
                                     kernel_call_seconds)


def compute(ctx):
    c = ctx["counters"]
    steps = c.get("serving/step_time{phase=decode}:count", 0)
    kv_bytes = decode_kv_bytes(ctx["config"], c) if steps else None
    seconds, calls = kernel_call_seconds(ctx["events"], RAGGED_KERNEL)
    if not kv_bytes or not calls:
        return None
    least = kv_bytes / steps / ctx["peaks"]["hbm_bytes_per_s"]
    spent = seconds / calls * ctx["config"]["num_hidden_layers"]
    return 100.0 * least / spent
