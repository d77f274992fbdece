"""What the Falcon-H1 cell's readers need, computed from the
configuration's PUBLISHED shapes: the bytes a decode step and the
operations a prompt position have to spend on one layer's Mamba-2 state,
and the two SSD kernels' calls in a traced slice.

The state of one sequence in one layer is `mamba_n_heads x mamba_d_head x
mamba_d_state` numbers at `state_dtype` (32 x 128 x 256 x 4 B = 4,194,304
B at the published widths).  A decode step reads it once and writes it
once for every live row, whatever the context's length.  A prompt position
costs, in a chunk of `mamba_chunk_size` = L positions, its row of `C B^T`
over the chunk in each B/C group (`2 G L N` FLOP), its row of the masked
product with `dt x` in each head (`2 H L P`), its read of the state
(`C S`, `2 H N P`) and its part of the state's pass (`B^T (dt x)`, `2 H N
P`): 5,373,952 FLOP a position a layer at the published widths.  The
kernel makes `C B^T` once a head, not once a group, and the whole chunk's
products where the causal half would do: that shows as lost share, and no
share can pass 100%.
"""
import re

from benchmark.lib.afmoe_ops import _ITEMSIZE, kernel_call_seconds

__all__ = ["DECODE_KERNEL", "PREFILL_KERNEL", "is_ssd_kernel",
           "kernel_call_seconds", "state_bytes", "decode_bytes_per_row_layer",
           "prefill_flops_per_token_layer", "decode_rows", "prefill_calls"]

PREFILL_KERNEL = "ssd_prefill"            # the `pallas_call` names
DECODE_KERNEL = "ssd_decode"
_SLOTS = "serving/state_slot_steps{group=ssm}"
_SHAPE = re.compile(r"\w+\[([0-9,]+)\]")


def state_bytes(config):
    """The published state of one sequence in one layer; None for a
    configuration without a Mamba-2 mixer."""
    if "mamba_d_state" not in config:
        return None
    return (int(config["mamba_n_heads"]) * int(config["mamba_d_head"])
            * int(config["mamba_d_state"]) * _ITEMSIZE[config["state_dtype"]])


def decode_bytes_per_row_layer(config):
    """Read once, written once."""
    size = state_bytes(config)
    return None if size is None else 2 * size


def prefill_flops_per_token_layer(config):
    if "mamba_d_state" not in config:
        return None
    chunk = int(config["mamba_chunk_size"])
    g, n = int(config["mamba_n_groups"]), int(config["mamba_d_state"])
    h, p = int(config["mamba_n_heads"]), int(config["mamba_d_head"])
    return 2 * (g * chunk * n + h * chunk * p + 2 * h * n * p)


def decode_rows(counters):
    """SSM state slots held, summed over the window's decode steps: the
    rows whose states a decode step had to move.  None where the program
    counts no `ssm` state group."""
    return counters.get(_SLOTS)


def is_ssd_kernel(name):
    from benchmark.lib.trace import is_pallas

    return is_pallas(name) and name.lstrip("%").startswith(
        (PREFILL_KERNEL, DECODE_KERNEL))


def prefill_calls(events):
    """[(rows, positions, seconds)] of the prefill kernel's calls in a
    traced slice.  A call's rows and positions are read from its first
    result in the HLO text the event keeps, `[rows, heads, chunks, chunk,
    head size]`: whole chunks (the cell's prompts are whole chunks)."""
    from benchmark.lib.trace import is_pallas

    out = []
    for evs in events["devices"].values():
        for name, _, dur in evs:
            if not (is_pallas(name)
                    and name.lstrip("%").startswith(PREFILL_KERNEL)):
                continue
            found = _SHAPE.search(name.partition(" = ")[2])
            dims = [int(x) for x in found.group(1).split(",")] if found else []
            if len(dims) == 5:
                out.append((dims[0], dims[0] * dims[2] * dims[3],
                            dur / 1e9))
    return out
