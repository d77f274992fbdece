"""Tokens an expert held here sees in a decode step, for a configuration
that names its experts `n_routed_experts` and its dense layers
`first_k_dense_replace` (`expert_tokens_per_step` reads `num_experts` and
`num_dense_layers`): the (token, expert) pairs the decode steps multiplied
here (`serving/moe_pairs{phase=decode,where=held}`) over decode steps,
expert layers and experts held.  mistral-small-4-ep8-l8's deployment gives
16 (8 x 64 rows x top-4 over 128), one chip's 64 rows 2.0.  Source:
program counters."""


def compute(ctx):
    c, cfg = ctx["counters"], ctx["config"]
    steps = c.get("serving/step_time{phase=decode}:count", 0)
    key = "serving/moe_pairs{phase=decode,where=held}"
    if not steps or key not in c or "n_routed_experts" not in cfg:
        return None
    layers = cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)
    return c[key] / (steps * layers * cfg["n_routed_experts"])
