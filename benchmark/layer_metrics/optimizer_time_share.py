"""Share of the device's operation time spent in the AdamW update fusions
(the rule that finds them: lib/trace.is_optimizer_update).  Source: device
trace."""
from benchmark.lib.trace import is_optimizer_update, share_of


def compute(ctx):
    share = share_of(ctx["events"], is_optimizer_update)
    return None if share is None else 100.0 * share
