"""Share of the device's operation time in the traced slice spent in the
latent decode kernel (the Mosaic call named `ragged_latent_attention`,
lib/mla_ops.py): how much of the cell's busy time is the mechanism the
cell was added for.  None where the slice holds no call of it.  Source:
device trace."""
from benchmark.lib.mla_ops import LATENT_KERNEL
from benchmark.lib.trace import is_pallas, share_of


def _is_latent_kernel(name):
    return is_pallas(name) and name.lstrip("%").startswith(LATENT_KERNEL)


def compute(ctx):
    events = ctx["events"]
    if not any(_is_latent_kernel(name) for evs in events["devices"].values()
               for name, _, _ in evs):
        return None
    return 100.0 * share_of(events, _is_latent_kernel)
