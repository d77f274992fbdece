"""What the lfm2_moe cell's two readers need, computed from the
configuration's shapes: the bytes of K/V a decode step's attention kernel
has to read, the bytes of one expert's weights, and which device
operations are the DECODE program's grouped products.

Telling the decode program's grouped products from the prefills'.  A
traced slice's `events` (lib/trace.py) keep an operation's HLO text, and
`jax.lax.ragged_dot` becomes on a TPU kernels named `%ragged-dot-...`
(lib/afmoe_ops.py).  The text carries no program name, but it carries the
result's shape, and the products' row count is the program's:
`held_experts_arrays` multiplies `tokens x num_experts_per_tok` rows, so
the decode program of `max_num_seqs` rows makes results `[max_num_seqs x
top_k, ...]` (256 in the cell `lfm2-24b-a2b-l9.agents-c64`: `bf16[256,1536]`
for an expert's gate and up matrices, `f32[256,2048]` for its down matrix),
and a whole-prompt prefill of P tokens `[P x top_k, ...]`: 1,024, 4,096 and
16,384 rows for the cell's prompts.  (A prefill of 256 tokens compiles a
second tier over 256 rows, `parallel.moe._SPLIT_ROWS`, that runs only when
at most a quarter of the pairs are held here; a chip that holds all 64
experts never takes it.)  An operation is the decode program's when the
first dimension of its result - of any member of a tuple result - is that
row count; the kernel's metadata call, whose result has other dimensions,
is not counted, so the time is a lower bound on the products' and the
share an upper one: a reading near 100% is a reason to look at the
`breakdown` for `ragged-dot` operations this filter misses.
"""
import re

from benchmark.lib.afmoe_ops import (RAGGED_KERNEL, _ITEMSIZE,
                                     is_grouped_product,
                                     kernel_call_seconds)

__all__ = ["RAGGED_KERNEL", "attention_layers", "decode_kv_bytes",
           "decode_product_rows", "expert_weight_bytes",
           "is_decode_grouped_product", "kernel_call_seconds",
           "kv_bytes_per_token_layer"]

_SHAPE = re.compile(r"[a-z]+[0-9]+\[(\d+),")


def attention_layers(config):
    """Layers with paged K/V: the entries of `layer_types` equal to
    `full_attention` (the others are convolutions and keep none)."""
    return sum(k == "full_attention" for k in config["layer_types"])


def kv_bytes_per_token_layer(config):
    """K and V of one token in one attention layer: 2 * K/V heads * head
    size * the bytes of the type served."""
    head = config.get("head_dim") or (config["hidden_size"]
                                      // config["num_attention_heads"])
    return (2 * config["num_key_value_heads"] * head
            * _ITEMSIZE[config["harness"]["dtype"]])


def decode_kv_bytes(config, counters):
    """Bytes of K/V the decode steps of a window had to read: the keys
    live in the full group (`serving/kv_tokens_live{group=full}`, a row's
    length, summed over decode steps) times the attention layers.  A lower
    bound: the kernel moves whole blocks."""
    key = "serving/kv_tokens_live{group=full}"
    if key not in counters:
        return None
    return (counters[key] * attention_layers(config)
            * kv_bytes_per_token_layer(config))


def expert_weight_bytes(config):
    """One expert's three matrices: gate and up [H, Im], down [Im, H]."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * _ITEMSIZE[config["harness"]["dtype"]])


def decode_product_rows(config, traffic):
    """Rows of the decode program's grouped products: every row of the
    fixed-shape batch times the experts a token selects."""
    return (int(traffic["engine"]["max_num_seqs"])
            * int(config["num_experts_per_tok"]))


def is_decode_grouped_product(name, rows):
    """A grouped product whose result has `rows` rows (the module
    docstring says why that names the decode program)."""
    if not is_grouped_product(name):
        return False
    _, _, rest = name.partition(" = ")
    result = rest.split(" ragged-dot", 1)[0].split(" custom-call", 1)[0]
    return any(int(n) == rows for n in _SHAPE.findall(result))
