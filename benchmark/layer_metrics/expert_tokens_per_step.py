"""Tokens an expert held here sees in a decode step: the (token, expert)
pairs the decode steps multiplied here (`serving/moe_pairs{phase=decode,
where=held}`) over decode steps, expert layers and experts held.  The
deployment's 8 x 32 rows give 4.0.  Source: program counters."""


def compute(ctx):
    c, cfg = ctx["counters"], ctx["config"]
    steps = c.get("serving/step_time{phase=decode}:count", 0)
    key = "serving/moe_pairs{phase=decode,where=held}"
    if not steps or key not in c:
        return None
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return c[key] / (steps * layers * cfg["num_experts"])
