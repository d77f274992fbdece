"""Model FLOP/s utilisation of training: the operations the forward and
backward passes need per token (lib/flops.py, recomputation not counted)
times this run's tokens per second, over chips times the bf16 peak of
lib/peaks.json.  The rate is the untraced window's."""
from benchmark.lib.flops import train_flops_per_token


def compute(ctx):
    rate = ctx["end_to_end"].get("train_tokens_per_s")
    if rate is None:
        return None
    per_token = train_flops_per_token(ctx["config"],
                                      ctx["timings"]["seq_len"])
    return 100.0 * rate * per_token / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
