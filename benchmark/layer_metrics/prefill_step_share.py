"""Share of the engine's step time, summed over the window, that went to
prefill steps (during which no running request gets a token).  Source:
program counter `serving/step_time`, every phase."""


def compute(ctx):
    sums = {k: v for k, v in ctx["counters"].items()
            if k.startswith("serving/step_time{") and k.endswith(":sum")}
    total = sum(sums.values())
    if not total:
        return None
    return 100.0 * sums.get("serving/step_time{phase=prefill}:sum", 0) / total
