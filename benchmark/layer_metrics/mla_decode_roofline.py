"""The latent decode kernel's share of its roofline: per decode step, the
larger of the time the chip's HBM needs for the latent rows the step has
to read (`serving/kv_tokens_live{group=latent}` over the window's decode
steps x the layers x 640 B a token a layer) and the time its MXU needs for
the two products every head takes with each row (x 32 heads x 2 x (320 +
256) FLOP), over the kernel's time a step (its mean call time in the
traced slice x the layers).  Bytes and operations are of the PUBLISHED row
(lib/mla_ops.py), whatever the pool pads it to, and a lower bound (whole
blocks move, the kernel's own block write is not counted), so the share
cannot pass 100%.  At 57.6 FLOP a byte against the chip's 240 the bytes
set it.  None where the program counts no latent group, the configuration
has no latent row or the slice holds no call of the kernel.  Source:
device trace."""
from benchmark.lib.mla_ops import (LATENT_KERNEL, decode_latent_tokens,
                                   kernel_call_seconds,
                                   latent_bytes_per_token_layer,
                                   latent_flops_per_token_layer,
                                   latent_layers)


def compute(ctx):
    c, cfg = ctx["counters"], ctx["config"]
    steps = c.get("serving/step_time{phase=decode}:count", 0)
    tokens = decode_latent_tokens(c)
    per_byte = latent_bytes_per_token_layer(cfg)
    if not steps or not tokens or per_byte is None:
        return None
    seconds, calls = kernel_call_seconds(ctx["events"], LATENT_KERNEL)
    if not calls:
        return None
    layers = latent_layers(cfg)
    rows = tokens / steps * layers
    least = max(rows * per_byte / ctx["peaks"]["hbm_bytes_per_s"],
                rows * latent_flops_per_token_layer(cfg)
                / ctx["peaks"]["bf16_flops_per_s"])
    return 100.0 * least / (seconds / calls * layers)
