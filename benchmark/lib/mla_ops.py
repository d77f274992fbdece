"""What the latent-attention cell's two readers need, computed from the
configuration's PUBLISHED shapes: the bytes and the operations the decode
kernel of a latent cache has to spend on one cached token of one layer,
and the kernel's name in a traced slice.

A cached token of a layer is one latent row: `kv_lora_rank +
qk_rope_head_dim` numbers (320: 640 B in bfloat16), read once.  Every
query head takes two products with it: its absorbed query against the
whole row (2 x 320 FLOP) and its weight times the row's first
`kv_lora_rank` lanes (2 x 256 FLOP): 32 heads x 2 x (320 + 256) = 36,864
FLOP a token a layer, 57.6 FLOP a byte against the v5e's 240.  Both count
the published row, whatever the pool pads it to (384 lanes here): padding
shows as lost share.
"""
from benchmark.lib.afmoe_ops import _ITEMSIZE, kernel_call_seconds

__all__ = ["LATENT_KERNEL", "decode_latent_tokens", "kernel_call_seconds",
           "latent_bytes_per_token_layer", "latent_flops_per_token_layer",
           "latent_layers"]

LATENT_KERNEL = "ragged_latent_attention"   # its `pallas_call` name
_LIVE = "serving/kv_tokens_live{group=latent}"


def _row(config):
    """(key lanes, value lanes) of a latent row, None for a configuration
    without one."""
    if "kv_lora_rank" not in config or "qk_rope_head_dim" not in config:
        return None
    rank = int(config["kv_lora_rank"])
    return rank + int(config["qk_rope_head_dim"]), rank


def latent_layers(config):
    """Layers with a latent cache: all of them (no layer is dense in its
    attention)."""
    return int(config["num_hidden_layers"])


def latent_bytes_per_token_layer(config):
    row = _row(config)
    if row is None:
        return None
    return row[0] * _ITEMSIZE[config["harness"]["dtype"]]


def latent_flops_per_token_layer(config):
    row = _row(config)
    if row is None:
        return None
    return int(config["num_attention_heads"]) * 2 * (row[0] + row[1])


def decode_latent_tokens(counters):
    """Latent rows the decode steps of a window had to read, a layer: a
    row's length, summed over rows and decode steps.  None where the
    program counts no latent group."""
    return counters.get(_LIVE)
