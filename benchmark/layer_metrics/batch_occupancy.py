"""Rows of the fixed-shape decode program that held a request:
`serving/decode_tokens` over decode steps times `max_num_seqs`, over the
window.  Source: program counters."""


def compute(ctx):
    c = ctx["counters"]
    steps = c.get("serving/step_time{phase=decode}:count", 0)
    if not steps:
        return None
    return 100.0 * c.get("serving/decode_tokens", 0) / (
        steps * ctx["timings"]["max_num_seqs"])
