"""The expert layer's grouped products' share of their memory roofline in
a decode step: the weight bytes of the experts a decode step touched
(`serving/moe_experts_touched{phase=decode}`, summed over the expert
layers, over the window's decode steps, x one expert's three matrices:
lib/lfm2_ops.expert_weight_bytes) over the chip's HBM rate, over the time a
decode step spends in the grouped products: the summed time of the decode
program's `ragged-dot` operations in the traced slice
(lib/lfm2_ops.is_decode_grouped_product tells them from the prefills' by
the row count in the HLO text) over the slice's decode steps (the calls of
the ragged decode kernel, which only the decode program makes, over the
attention layers).  Bound by memory: 4 rows an expert against 18.9 MB of
its weights.  The bytes are a lower bound (activations are not counted),
so the share cannot pass 100%; a reading near it means the filter misses
operations.  None where it can name no such operation, no kernel call or
no counter.  Source: device trace."""
from benchmark.lib.lfm2_ops import (RAGGED_KERNEL, attention_layers,
                                    decode_product_rows, expert_weight_bytes,
                                    is_decode_grouped_product,
                                    kernel_call_seconds)


def compute(ctx):
    c, cfg = ctx["counters"], ctx["config"]
    steps = c.get("serving/step_time{phase=decode}:count", 0)
    touched = c.get("serving/moe_experts_touched{phase=decode}")
    if not steps or not touched or "layer_types" not in cfg:
        return None
    rows = decode_product_rows(cfg, ctx["traffic"])
    spent = sum(dur for evs in ctx["events"]["devices"].values()
                for name, _, dur in evs
                if is_decode_grouped_product(name, rows)) / 1e9
    _, calls = kernel_call_seconds(ctx["events"], RAGGED_KERNEL)
    if not spent or not calls:
        return None
    steps_in_slice = calls / attention_layers(cfg)
    least = (touched / steps * expert_weight_bytes(cfg)
             / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (spent / steps_in_slice)
