"""Share of the traced slice in which no operation ran on the device:
100 * (1 - busy/slice), busy being the union of the `XLA Ops` events of a
chip, averaged over chips (lib/trace.py).  Source: device trace."""


def compute(ctx):
    return 100.0 * ctx["trace"]["idle_share"]
