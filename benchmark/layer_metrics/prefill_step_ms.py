"""Mean host-clock time of an engine step that prefilled one prompt, over
the window: `serving/step_time{phase=prefill}`, sum over count.  Source:
program counter."""


def compute(ctx):
    c = ctx["counters"]
    n = c.get("serving/step_time{phase=prefill}:count", 0)
    if not n:
        return None
    return 1e3 * c["serving/step_time{phase=prefill}:sum"] / n
