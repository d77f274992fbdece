"""Mean host-clock time of an engine step that decoded, over the window:
`serving/step_time{phase=decode}`, sum over count (the histogram keeps no
samples, so no median can be had from it).  Source: program counter."""


def compute(ctx):
    c = ctx["counters"]
    n = c.get("serving/step_time{phase=decode}:count", 0)
    if not n:
        return None
    return 1e3 * c["serving/step_time{phase=decode}:sum"] / n
