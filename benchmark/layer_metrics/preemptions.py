"""Requests the scheduler preempted inside the window for want of KV
blocks: `serving/preemptions`.  Source: program counter."""


def compute(ctx):
    if not ctx["counters"]:
        return None
    return ctx["counters"].get("serving/preemptions", 0)
