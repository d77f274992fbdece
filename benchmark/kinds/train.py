"""kind `train`: the compiled train step of a decoder, driven as a training
loop drives it - dispatch the next step, then wait for the one before, so
one step is always in flight.

Traffic parameters (benchmark/traffic/<name>.json): seq_len, micro_batch,
learning_rate, multi_precision, distinct_batches, trace_steps.

The model is built by `lib.common.build_model` and placed, stepped and
optimised through the program's user entry points: `parallel.init_mesh`,
`parallel.place_model`, `optimizer.AdamW`, `jit.compile(step, models,
optimizers)`.  Every step gets another seeded batch of uniform random ids
and labels, all made before the window.

Window: steps are dispatched until `seconds` have passed; the window ends
when the last dispatched step completes (`block_until_ready`), and the
rate is every step's tokens over that whole time.

correct: the first step's loss equals the plain reference's on the same
weights and batch within LOSS_TOLERANCE; every loss is finite; nothing
compiled inside the window; the step contains the flash kernel and no
attention gate fell back.
"""
import math
import time

from benchmark.lib import reference_gpt
from benchmark.lib.common import (Laps, build_model, fold_seed, log,
                                  profiler_slice)

# |first loss - reference|.  The step runs in bf16, the reference in
# float32; over the >= 4096 positions of a batch the rounding averages out
# and the chip showed at most 3.4e-4 in 15 runs (PERF.md, PR 24).  Six times
# that is still far under what dropping a layer or a mask moves the loss.
LOSS_TOLERANCE = 2e-3


def run(spec):
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    import paddle_tpu as paddle
    from paddle_tpu import jit, optimizer, parallel
    from paddle_tpu.models import GPTPretrainingCriterion
    from paddle_tpu.ops.pallas_ops import attention_path_counts

    tr = spec["traffic"]
    batch, seq = int(tr["micro_batch"]), int(tr["seq_len"])
    lap = Laps(spec["t0"])
    parallel.init_mesh()
    model, cfg = build_model(spec["config"], spec["seed"])
    parallel.place_model(model)
    crit = GPTPretrainingCriterion(cfg)
    opt = optimizer.AdamW(learning_rate=float(tr["learning_rate"]),
                          parameters=model.parameters(),
                          multi_precision=bool(tr["multi_precision"]))

    def step(x, y):
        loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = jit.compile(step, models=[model], optimizers=[opt])
    rng = np.random.RandomState(fold_seed(spec["seed"]))
    n_batches = int(tr["distinct_batches"])
    data = rng.randint(0, cfg.vocab_size,
                       (n_batches, 2, batch, seq)).astype("int32")
    batches = [tuple(parallel.shard_tensor(paddle.to_tensor(a),
                                           (("dp", "sharding"), None))
                     for a in pair) for pair in data]
    jax.block_until_ready([p._data for p in model.parameters()])
    lap("weights")

    # the reference first: the step donates the weights it reads
    ref_loss = float(reference_gpt.mean_cross_entropy(
        reference_gpt.params_from_model(model), batches[0][0]._data,
        batches[0][1]._data, heads=cfg.num_attention_heads,
        eps=float(cfg.layer_norm_epsilon)))
    lap("reference")
    first_loss = float(compiled(*batches[0]))
    lap("compile_or_cache")
    for pair in batches[1:3]:
        compiled(*pair)._data.block_until_ready()
    lap("warmup")

    cursor = 3

    def drive(stop_at=None, steps=None):
        """Run steps with one in flight until the clock passes `stop_at`
        or `steps` were dispatched; -> (loss arrays, completion times)."""
        nonlocal cursor
        losses, ends, pending, sent = [], [], None, 0

        def wait():
            with TraceAnnotation("bench:wait"):
                pending._data.block_until_ready()
            ends.append(time.perf_counter())
            losses.append(pending)

        while (time.perf_counter() < stop_at if steps is None
               else sent < steps):
            with TraceAnnotation("bench:dispatch"):
                loss = compiled(*batches[cursor % n_batches])
            cursor += 1
            sent += 1
            if pending is not None:
                wait()
            pending = loss
        if pending is not None:
            wait()
        return losses, ends

    compiles_before = spec["compiles"].compiles
    t_begin = time.perf_counter()
    setup_s = t_begin - spec["t0"]
    losses, ends = drive(stop_at=t_begin + spec["seconds"])
    window_s = ends[-1] - t_begin
    compiles_in_window = spec["compiles"].compiles - compiles_before
    tokens_per_s = len(ends) * batch * seq / window_s

    traced = {}
    if spec["trace"]:
        with profiler_slice(traced):
            drive(steps=int(tr["trace_steps"]))

    values = [float(x) for x in losses]
    paths = attention_path_counts()
    fallbacks = {k: v for k, v in paths.items() if "_fallback:" in k}
    checks = {
        "loss_vs_reference": abs(first_loss - ref_loss) <= LOSS_TOLERANCE,
        "losses_finite": all(math.isfinite(v) for v in values)
        and math.isfinite(first_loss),
        "no_compile_in_window": compiles_in_window == 0,
        "kernel_paths": paths.get("attn_kernel", 0) >= 1 and not fallbacks,
    }
    steps_s = [b - a for a, b in zip(ends, ends[1:])]
    log(f"first loss {first_loss:.5f}, reference {ref_loss:.5f} (|diff| "
        f"{abs(first_loss - ref_loss):.2e}, tolerance {LOSS_TOLERANCE})")
    log(f"{len(ends)} steps of {batch}x{seq} in {window_s:.3f} s; step "
        f"median {sorted(steps_s)[len(steps_s) // 2] if steps_s else 0:.4f}"
        f" s; losses {values[0]:.4f} .. {values[-1]:.4f}; compiles in "
        f"window {compiles_in_window}; attention paths {paths}")
    log(f"set-up split (s): {lap}")
    return {
        "checks": checks,
        "attempted": len(values),
        "failed": sum(not math.isfinite(v) for v in values),
        "end_to_end": {"train_tokens_per_s": tokens_per_s,
                       "setup_s": setup_s},
        "timings": {"window_s": window_s, "steps": len(ends),
                    "tokens_per_step": batch * seq, "seq_len": seq,
                    "step_s": steps_s, "setup_split_s": lap.split,
                    "compiles_in_window": compiles_in_window},
        "counters": {},
        "traced": traced,
    }
