"""Share of the device's operation time spent in the expert layer's
grouped products: XLA's `ragged-dot` kernels, which `jax.lax.ragged_dot`
over the held experts' three matrices becomes (lib/afmoe_ops.py says why
the router, the sort, the scatter-add and the shared expert are not in it:
the trace as read here carries no scope).  Source: device trace."""
from benchmark.lib.afmoe_ops import is_grouped_product
from benchmark.lib.trace import share_of


def compute(ctx):
    if not any(is_grouped_product(name)
               for evs in ctx["events"]["devices"].values()
               for name, _, _ in evs):
        return None
    return 100.0 * share_of(ctx["events"], is_grouped_product)
