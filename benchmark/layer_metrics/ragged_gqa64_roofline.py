"""The ragged decode kernel's share of its memory roofline at grouped
heads of 64 lanes (32 query heads over 8 K/V heads, two K/V heads a lane
tile: `ops/ragged_paged_attention._pair_members`): the bytes of K/V a
decode step has to read (lib/lfm2_ops.decode_kv_bytes over the window's
decode steps: live keys x attention layers x 2 KB a token a layer) over
the chip's HBM rate, over the kernel's time a step (its mean call time in
the traced slice x the attention layers).  The bytes are a lower bound
(the kernel moves whole blocks of 64 tokens, and its own block writes are
not counted), so the share cannot pass 100%.  Bound by memory: 8 query
rows a K/V head pair are too few products to count.  None where the
program counts no `serving/kv_tokens_live{group=full}` or the slice holds
no call of the kernel.  Source: device trace."""
from benchmark.lib.lfm2_ops import (RAGGED_KERNEL, attention_layers,
                                    decode_kv_bytes, kernel_call_seconds)


def compute(ctx):
    c = ctx["counters"]
    steps = c.get("serving/step_time{phase=decode}:count", 0)
    if not steps or "layer_types" not in ctx["config"]:
        return None
    kv_bytes = decode_kv_bytes(ctx["config"], c)
    seconds, calls = kernel_call_seconds(ctx["events"], RAGGED_KERNEL)
    if not kv_bytes or not calls:
        return None
    least = kv_bytes / steps / ctx["peaks"]["hbm_bytes_per_s"]
    spent = seconds / calls * attention_layers(ctx["config"])
    return 100.0 * least / spent
