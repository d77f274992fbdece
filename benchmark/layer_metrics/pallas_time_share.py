"""Share of the device's operation time spent in Pallas (Mosaic) kernels:
the HLO custom-calls (lib/trace.is_pallas).  Source: device trace."""
from benchmark.lib.trace import is_pallas, share_of


def compute(ctx):
    share = share_of(ctx["events"], is_pallas)
    return None if share is None else 100.0 * share
