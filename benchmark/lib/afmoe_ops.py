"""What the afmoe cells' per-layer readers need: which device operations
are the expert layer's grouped products, and the bytes a decode step's
attention kernel has to read - computed from the configuration's shapes.

Operation names.  A traced slice's `events` (lib/trace.py) keep an
operation's HLO text, which names a Pallas kernel after its `name`
(`%ragged_paged_attention.3`, `%flash_fwd.1`) and XLA's own grouped-matmul
kernels `%ragged-dot-...` (what `jax.lax.ragged_dot` becomes on a TPU: a
metadata call and one product a matrix).  The `jax.named_scope` an
operation was traced under (`afmoe/router`, `afmoe/experts`, ...) is NOT
in what `jax.profiler.ProfileData` hands out: a device event's stats are
its offset, its duration and a time scale (read on the chip, PR 28), so the
router's, the sort's, the scatter-add's and the shared expert's fusions
cannot be told from other fusions here, and `moe_time_share` counts the
grouped products alone.
"""
GROUPED_PRODUCT = "ragged-dot"          # in the HLO name of XLA's kernel
RAGGED_KERNEL = "ragged_paged_attention"
_ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def is_grouped_product(name):
    """An event of the expert layer's grouped products, by its HLO
    instruction's name."""
    return GROUPED_PRODUCT in name.partition(" = ")[0]


def kv_bytes_per_token_layer(config):
    """K and V of one token in one layer: 2 * K/V heads * head size * the
    bytes of the type served."""
    return (2 * config["num_key_value_heads"] * config["head_dim"]
            * _ITEMSIZE[config["harness"]["dtype"]])


def layers_by_group(config):
    """{"full": layers that keep the whole sequence, "window": sliding}."""
    kinds = config["layer_types"]
    window = sum(k == "sliding_attention" for k in kinds)
    return {"full": len(kinds) - window, "window": window}


def decode_kv_bytes(config, counters):
    """Bytes of K/V the decode steps of a window had to read: per cache
    group, the keys live in it (`serving/kv_tokens_live{group}`, a row's
    length or min(length, window)) times its layers.  A lower bound: the
    kernel moves whole blocks."""
    per = kv_bytes_per_token_layer(config)
    layers = layers_by_group(config)
    if not any(f"serving/kv_tokens_live{{group={g}}}" in counters
               for g in layers):
        return None
    return sum(counters.get(f"serving/kv_tokens_live{{group={g}}}", 0)
               * n * per for g, n in layers.items())


def kernel_call_seconds(events, kernel):
    """(summed seconds, calls) of the Pallas kernel named `kernel`."""
    from benchmark.lib.trace import is_pallas

    total, calls = 0.0, 0
    for evs in events["devices"].values():
        for name, _, dur in evs:
            if is_pallas(name) and name.lstrip("%").startswith(kernel):
                total += dur / 1e9
                calls += 1
    return total, calls
