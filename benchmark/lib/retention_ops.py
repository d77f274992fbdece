"""What the power-retention cell's readers need, computed from the
configuration's PUBLISHED shapes: the bytes a decode step and the
operations a prompt position have to spend on one layer's retention, and
the two kernels' names in a traced slice.

The state of one sequence in one layer is `S` and `z` over the
`head_dim (head_dim + 1) / 2` distinct pairs of a key's lanes, a K/V head:
`num_key_value_heads x 8256 x 129` numbers at `state_dtype` (34.08 MB at
the published widths).  A decode step reads it once and writes it once for
every live row, whatever the context's length.  A prompt position takes
every query head's `phi(q)` against `S` and `z` and adds `phi(k) [v, 1]^T`
to them: `2 x 8256 x 129 x (query heads + K/V heads)` FLOP through the
state, and inside its chunk `2 x (head_dim + head_dim + 1)` FLOP a query
head for each key it sees there (on average half of CHUNK, the chunk the
kernel takes; tests/benchmark/test_brumby_cell.py holds it to
`ops/power_retention.PREFILL_CHUNK`).  All of it counts the published
state, whatever the pool pads it to (65 tiles of 136 x 128 here, +6.3%):
padding shows as lost share, and no share can pass 100%.
"""
import re

from benchmark.lib.afmoe_ops import _ITEMSIZE, kernel_call_seconds

__all__ = ["DECODE_KERNEL", "PREFILL_KERNEL", "CHUNK", "is_retention_kernel",
           "kernel_call_seconds", "retention_layers", "state_bytes",
           "decode_bytes_per_row_layer", "prefill_flops_per_token_layer",
           "decode_rows", "prefill_calls"]

PREFILL_KERNEL = "retention_prefill"      # the `pallas_call` names
DECODE_KERNEL = "retention_decode"
CHUNK = 256
_SLOTS = "serving/state_slot_steps{group=retention}"
_SHAPE = re.compile(r"\w+\[([0-9,]+)\]")


def _pairs(config):
    d = int(config["head_dim"])
    return d * (d + 1) // 2


def retention_layers(config):
    """Layers with a retention state: all of them."""
    return int(config["num_hidden_layers"])


def state_bytes(config):
    """The published state of one sequence in one layer; None for a
    configuration without one."""
    if "retention_degree" not in config:
        return None
    return (int(config["num_key_value_heads"]) * _pairs(config)
            * (int(config["head_dim"]) + 1)
            * _ITEMSIZE[config["state_dtype"]])


def decode_bytes_per_row_layer(config):
    """Read once, written once."""
    size = state_bytes(config)
    return None if size is None else 2 * size


def prefill_flops_per_token_layer(config):
    if "retention_degree" not in config:
        return None
    d = int(config["head_dim"])
    hq, hkv = (int(config["num_attention_heads"]),
               int(config["num_key_value_heads"]))
    through_state = 2 * _pairs(config) * (d + 1) * (hq + hkv)
    in_chunk = hq * 2 * (d + d + 1) * (CHUNK + 1) / 2
    return through_state + in_chunk


def decode_rows(counters):
    """State slots held, summed over the window's decode steps: the rows
    whose states a decode step had to move.  None where the program counts
    no retention group."""
    return counters.get(_SLOTS)


def is_retention_kernel(name):
    from benchmark.lib.trace import is_pallas

    return is_pallas(name) and name.lstrip("%").startswith(
        (PREFILL_KERNEL, DECODE_KERNEL))


def prefill_calls(events, config):
    """[(positions, seconds)] of the prefill kernel's calls in a traced
    slice.  A call's positions are read from its first result in the HLO
    text the event keeps, `[rows, K/V heads, chunks, query heads a K/V head
    x CHUNK, head_dim]`: whole chunks (a prompt's last chunk counts whole;
    the cell's prompts are whole chunks)."""
    from benchmark.lib.trace import is_pallas

    groups = (int(config["num_attention_heads"])
              // int(config["num_key_value_heads"]))
    out = []
    for evs in events["devices"].values():
        for name, _, dur in evs:
            if not (is_pallas(name)
                    and name.lstrip("%").startswith(PREFILL_KERNEL)):
                continue
            found = _SHAPE.search(name.partition(" = ")[2])
            dims = [int(x) for x in found.group(1).split(",")] if found else []
            if len(dims) == 5:
                out.append((dims[0] * dims[2] * dims[3] // groups,
                            dur / 1e9))
    return out
