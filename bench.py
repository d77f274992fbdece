"""Benchmarks on one chip.

Default run (what the driver invokes): the HEADLINE metric — GPT-2 124M
pretrain step throughput — printed as ONE JSON line
{"metric", "value", "unit", "vs_baseline"}.

`python bench.py --config <name>` runs one BASELINE.md ladder config and
prints its line.  `python bench.py --ladder` runs every ladder config in a
fresh subprocess (isolated HBM), writes BENCH_LADDER.json and exits
non-zero if any config errored or timed out; the driver's default
invocation stays headline-only so its timeout budget is untouched.

vs_baseline normalizes tokens/sec (or images/sec) against a 40%-MFU run of
the same model on this chip's bf16 peak — the reference publishes no
absolute numbers (BASELINE.md), and 40% MFU is what a well-tuned
A100+NCCL job typically sustains, i.e. vs_baseline >= 1.0 means "at or
above A100-class utilization".
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

PEAK_BF16 = 197e12  # v5e


def _on_tpu():
    import jax

    return jax.devices()[0].platform == "tpu"


def _history_path():
    """BENCH_HISTORY.jsonl location (next to this file).  Override with
    PTPU_BENCH_HISTORY=<path>; disable with PTPU_BENCH_HISTORY=0."""
    p = os.environ.get("PTPU_BENCH_HISTORY")
    if p is not None and p.strip().lower() in ("0", "off", "none", ""):
        return None
    return p or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_HISTORY.jsonl")


_LEDGER_TAGS = None


def _ledger_tags():
    """host/backend/commit — constant for the process lifetime, computed
    once (a ladder run emits a dozen metrics; one git subprocess each
    would dominate the append)."""
    global _LEDGER_TAGS
    if _LEDGER_TAGS is not None:
        return _LEDGER_TAGS
    import socket

    tags = {}
    try:
        tags["host"] = socket.gethostname()
    except OSError:
        tags["host"] = "unknown"
    import jax

    tags["backend"] = jax.default_backend()
    try:
        tags["commit"] = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=15).stdout.strip() or None
    except (OSError, subprocess.SubprocessError):
        tags["commit"] = None
    _LEDGER_TAGS = tags
    return tags


def _ledger(line):
    """Append the emitted line to the persistent bench ledger, tagged with
    host/backend/commit so `check_bench_regression.py --history` can gate
    the current run against the trailing median of COMPARABLE runs (same
    host, same backend — a host change is a new lane, never a regression).
    Best-effort: a full disk or read-only checkout must not fail the
    bench itself."""
    path = _history_path()
    if path is None:
        return
    import datetime

    rec = dict(line)
    rec["ts"] = datetime.datetime.now(
        datetime.timezone.utc).isoformat(timespec="seconds")
    rec.update(_ledger_tags())
    rec["cpu_smoke"] = ("smoke" in rec.get("metric", "")
                        or "skipped_cpu" in rec.get("metric", ""))
    try:
        with open(path, "a") as f:
            f.write(json.dumps(rec) + "\n")
    except OSError as e:
        print(f"bench: ledger append failed ({e})", file=sys.stderr)


def _emit(metric, value, unit, baseline):
    line = {
        "metric": metric,
        "value": round(value, 2),
        "unit": unit,
        "vs_baseline": round(value / baseline, 4) if baseline else 0.0,
    }
    print(json.dumps(line))
    _ledger(line)
    return line


def _time_steps(compiled, args, steps, warmup):
    for _ in range(warmup):
        out = compiled(*args)
    _ = float(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = compiled(*args)
    _ = float(out)
    return (time.perf_counter() - t0) / steps


def _gpt_step(cfg, batch, seq, lr=1e-4, multi_precision=True):
    import paddle_tpu as paddle
    from paddle_tpu import jit, optimizer, parallel
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion

    paddle.seed(0)
    parallel.init_mesh()
    model = parallel.place_model(GPTForCausalLM(cfg))
    if _on_tpu():
        model.bfloat16()
    crit = GPTPretrainingCriterion(cfg)
    opt = optimizer.AdamW(learning_rate=lr, parameters=model.parameters(),
                          multi_precision=multi_precision)

    def step(x, y):
        loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = jit.compile(step, models=[model], optimizers=[opt])
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    lab = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return compiled, (ids, lab), n_params


def bench_gpt124m():
    """Headline: north-star metric at 124M scale (BASELINE.md config 4's
    little sibling, runnable fast every round)."""
    from paddle_tpu.models import gpt2_124m_config, gpt_test_config

    on_tpu = _on_tpu()
    if on_tpu:
        cfg = gpt2_124m_config(stacked_blocks=True, max_position_embeddings=1024)
        batch, seq, steps, warmup = 8, 1024, 10, 3
    else:  # CPU smoke fallback so the bench always emits a line
        cfg = gpt_test_config(num_hidden_layers=2, stacked_blocks=True)
        batch, seq, steps, warmup = 4, 32, 3, 1

    # bf16 params/compute with fp32 master weights in AdamW — the
    # north-star precision recipe (SURVEY §8.12)
    compiled, args, n_params = _gpt_step(cfg, batch, seq)
    dt = _time_steps(compiled, args, steps, warmup)
    tokens_per_sec = batch * seq / dt
    peak = PEAK_BF16 if on_tpu else 5e9
    baseline = 0.40 * peak / (6.0 * n_params)
    return _emit(
        "gpt_124m_pretrain_tokens_per_sec_per_chip" if on_tpu
        else "gpt_tiny_pretrain_tokens_per_sec_cpu_smoke",
        tokens_per_sec, "tokens/sec", baseline)


def bench_gpt3_1p3b():
    """BASELINE.md config 4 at single-chip scale: 1.3B params, seq 2048.
    bf16 AdamW moments (multi_precision=False) so states fit one chip's
    HBM; the fleet DP version of this config is the v5e-16 north star."""
    from paddle_tpu.models import gpt3_1p3b_config

    if not _on_tpu():
        return _emit("gpt3_1p3b_skipped_cpu", 0.0, "tokens/sec", 0.0)
    cfg = gpt3_1p3b_config(stacked_blocks=True)
    batch, seq = 2, 2048
    compiled, args, n_params = _gpt_step(cfg, batch, seq,
                                         multi_precision=False)
    dt = _time_steps(compiled, args, steps=5, warmup=2)
    baseline = 0.40 * PEAK_BF16 / (6.0 * n_params)
    return _emit("gpt3_1p3b_pretrain_tokens_per_sec_per_chip",
                 batch * seq / dt, "tokens/sec", baseline)


def bench_bert_base():
    """BASELINE.md config 3: BERT-base fine-tune step (cls head)."""
    import paddle_tpu as paddle
    from paddle_tpu import jit, optimizer, parallel
    from paddle_tpu.models import BertForSequenceClassification, bert_base_config

    on_tpu = _on_tpu()
    drop = dict(hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    cfg = (bert_base_config(**drop) if on_tpu else bert_base_config(
        hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
        intermediate_size=128, vocab_size=512, **drop))
    batch, seq = (32, 128) if on_tpu else (2, 16)
    paddle.seed(0)
    parallel.init_mesh()
    model = parallel.place_model(BertForSequenceClassification(cfg, num_classes=2))
    if on_tpu:
        model.bfloat16()
    opt = optimizer.AdamW(learning_rate=2e-5, parameters=model.parameters())

    def step(ids, labels):
        logits = model(ids)
        loss = paddle.nn.functional.cross_entropy(logits, labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = jit.compile(step, models=[model], optimizers=[opt])
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    lab = paddle.to_tensor(rng.randint(0, 2, (batch,)).astype("int64"))
    dt = _time_steps(compiled, (ids, lab), steps=10 if on_tpu else 2,
                     warmup=3 if on_tpu else 1)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    peak = PEAK_BF16 if on_tpu else 5e9
    baseline = 0.40 * peak / (6.0 * n_params)
    return _emit("bert_base_finetune_tokens_per_sec_per_chip",
                 batch * seq / dt, "tokens/sec", baseline)


def bench_resnet50():
    """BASELINE.md config 2: ResNet-50 train step (the conv/BN/pool path),
    compiled whole-step — the Executor static-graph analog.

    On TPU the network is built channels-last (NHWC) with bf16 inputs:
    channels ride the lane dimension of the (8,128) vector tiling, so
    convs hit the MXU without compiler-inserted relayouts (the cuDNN
    autotuned-layout analog, VERDICT r2 weak #2). A/B knobs:
    PTPU_RESNET_BENCH_FORMAT=NCHW, PTPU_RESNET_BENCH_BATCH=N."""
    import paddle_tpu as paddle
    from paddle_tpu import jit, optimizer, parallel
    from paddle_tpu.vision.models import resnet50

    on_tpu = _on_tpu()
    batch = int(os.environ.get("PTPU_RESNET_BENCH_BATCH", 64 if on_tpu else 2))
    fmt = os.environ.get("PTPU_RESNET_BENCH_FORMAT",
                         "NHWC" if on_tpu else "NCHW")
    size = 224 if on_tpu else 32
    paddle.seed(0)
    parallel.init_mesh()
    model = parallel.place_model(resnet50(num_classes=1000, data_format=fmt))
    if on_tpu:
        model.bfloat16()
    opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                             parameters=model.parameters())

    def step(x, y):
        loss = paddle.nn.functional.cross_entropy(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = jit.compile(step, models=[model], optimizers=[opt])
    rng = np.random.RandomState(0)
    shape = ((batch, 3, size, size) if fmt == "NCHW"
             else (batch, size, size, 3))
    x_np = rng.randn(*shape).astype("float32")
    x = paddle.to_tensor(x_np)
    if on_tpu:
        x = x.astype("bfloat16")  # bf16 images: conv inputs stay MXU-native
    y = paddle.to_tensor(rng.randint(0, 1000, (batch,)).astype("int64"))
    dt = _time_steps(compiled, (x, y), steps=10 if on_tpu else 2,
                     warmup=3 if on_tpu else 1)
    # ResNet-50 fwd ~4.1 GFLOP/image at 224^2; train ~3x fwd
    flops_per_image = 3 * 4.1e9 * (size / 224) ** 2
    peak = PEAK_BF16 if on_tpu else 5e9
    baseline = 0.40 * peak / flops_per_image
    return _emit("resnet50_train_images_per_sec_per_chip",
                 batch / dt, "images/sec", baseline)


def bench_decode():
    """Autoregressive decode throughput (KV-cache + flash-decode kernel):
    generated tokens/sec on GPT-2 124M. Baseline = HBM-bandwidth-bound
    decode: each token streams the 124M bf16 weights once (~0.25 GB) at
    the v5e's ~819 GB/s, so ~3300 tokens/sec/sequence ideal; at batch 8
    weights amortize across the batch."""
    import paddle_tpu as paddle
    from paddle_tpu import parallel
    from paddle_tpu.models import GPTForCausalLM, gpt2_124m_config, gpt_test_config

    on_tpu = _on_tpu()
    cfg = (gpt2_124m_config(stacked_blocks=True) if on_tpu
           else gpt_test_config(num_hidden_layers=2, stacked_blocks=True,
                                max_position_embeddings=64))
    batch, prompt, new = (8, 128, 128) if on_tpu else (2, 8, 8)
    # A/B knobs: prompt length sets S_max (where
    # the prefix-reading Pallas kernel separates from the XLA full-cache
    # path); batch amortizes per-step fixed costs across sequences
    batch = int(os.environ.get("PTPU_DECODE_BENCH_BATCH", batch))
    prompt = int(os.environ.get("PTPU_DECODE_BENCH_PROMPT", prompt))
    new = int(os.environ.get("PTPU_DECODE_BENCH_NEW", new))
    paddle.seed(0)
    parallel.init_mesh()
    model = parallel.place_model(GPTForCausalLM(cfg))
    if on_tpu:
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, prompt)).astype("int32"))
    # warmup MUST use the same max_new_tokens: generate's executable cache
    # keys on total length (prefill + decode cache shapes)
    model.generate(ids, max_new_tokens=new)
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=new)
    _ = out.numpy()
    dt = time.perf_counter() - t0
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    hbm_bw = 819e9 if on_tpu else 50e9
    baseline = batch * hbm_bw / (2.0 * n_params)   # bf16 weight stream/step
    if os.environ.get("PTPU_ATTN_DEBUG") == "1":
        from paddle_tpu.ops.pallas_ops import attention_path_counts

        print(f"attn paths: {attention_path_counts()}", file=sys.stderr)
    return _emit("gpt_124m_decode_tokens_per_sec" if on_tpu
                 else "gpt_tiny_decode_tokens_per_sec_cpu_smoke",
                 batch * new / dt, "tokens/sec", baseline)


def bench_lowbit_kv_decode():
    """paddle_tpu.lowbit KV wing: paged-serving decode throughput with an
    int8-quantized KV cache vs the fp pool, plus the capacity win
    (blocks-per-pool at the same byte budget — the quantized pool must
    hold ≥1.9× the blocks).  Baseline for the headline tokens/s metric is
    the SAME engine with full-precision KV, so vs_baseline ≈ 1.0 means
    quantized decode is free and the capacity win is pure profit."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config, \
        gpt2_124m_config
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    on_tpu = _on_tpu()
    cfg = (gpt2_124m_config(stacked_blocks=True) if on_tpu
           else gpt_test_config(stacked_blocks=True,
                                sequence_parallel=False))
    batch, prompt, new = (8, 128, 128) if on_tpu else (4, 8, 16)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (prompt,)).astype("int32")
               for _ in range(batch)]
    sp = SamplingParams(max_new_tokens=new)

    def tps(kv_dtype):
        eng = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=batch, kv_cache_dtype=kv_dtype))
        eng.generate(prompts, sp)          # warmup: compiles every bucket
        t0 = time.perf_counter()
        eng.generate(prompts, sp)
        dt = time.perf_counter() - t0
        return batch * new / dt, eng.cache

    fp_tps, fp_cache = tps(None)
    q_tps, q_cache = tps("int8")
    _emit("serving_kv_int8_blocks_per_pool",
          q_cache.num_blocks / fp_cache.num_blocks, "x blocks (same bytes)",
          1.0)
    suffix = "" if on_tpu else "_cpu_smoke"
    return _emit(f"serving_kv_int8_decode_tokens_per_sec{suffix}",
                 q_tps, "tokens/sec", fp_tps)


def bench_prefix_prefill():
    """ISSUE 15a: cold-vs-hot TTFT for a shared-prefix workload.

    One prefix-caching engine; TTFT (add_request → first token) is
    measured per request, min over interleaved cold/hot reps (the PR-7
    noise discipline: a min of single-program walls is gateable where
    whole-generate walls drift >50% on this host).  A COLD request
    carries a fresh never-seen prefix (pays the full prefill and
    registers it); a HOT request reuses the warmed base prefix and pays
    only its tail chunk.  Emits the cold/hot TTFT ratio with baseline
    1.0 — higher is better; a prefix-cache regression (hit path
    recomputing the prefix) drags it toward 1."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config, \
        gpt2_124m_config
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    on_tpu = _on_tpu()
    # CPU: the tiny config but with a 256-position window — at the
    # default 64 the saved prefill (a few dozen tokens of a 64-wide
    # model) is smaller than the hot path's padded-extent attention and
    # the lane would time dispatch overhead, not the cache win
    cfg = (gpt2_124m_config(stacked_blocks=True) if on_tpu
           else gpt_test_config(stacked_blocks=True,
                                sequence_parallel=False,
                                max_position_embeddings=256))
    prefix_len, tail, new = (256, 32, 8) if on_tpu else (192, 16, 4)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4,
                                        enable_prefix_caching=True))
    rng = np.random.RandomState(0)
    sp = SamplingParams(max_new_tokens=new)

    def mk(prefix):
        return np.concatenate(
            [prefix, rng.randint(0, cfg.vocab_size, (tail,))
             .astype("int32")])

    def ttft(prompt):
        rid = eng.add_request(prompt, sp)
        try:
            t0 = time.perf_counter()
            while not eng._requests[rid].output_ids:
                eng.step()
            dt = time.perf_counter() - t0
            while eng.has_unfinished():
                eng.step()
            return dt
        finally:
            eng.release_request(rid)

    base = rng.randint(0, cfg.vocab_size, (prefix_len,)).astype("int32")
    ttft(mk(base))     # warm: compiles prefill(L), registers base
    ttft(mk(base))     # warm: compiles the hot tail continuation
    assert eng.cache.prefix_hits >= 1, "hot warmup did not hit"
    cold = hot = float("inf")
    for _ in range(3 if on_tpu else 5):
        # interleaved cold/hot so shared-host drift hits both lanes
        # alike; each cold rep uses a NEVER-SEEN prefix (hot recency
        # keeps the base chain off the LRU reclaim path)
        fresh = rng.randint(0, cfg.vocab_size,
                            (prefix_len,)).astype("int32")
        cold = min(cold, ttft(mk(fresh)))
        hot = min(hot, ttft(mk(base)))
    suffix = "" if on_tpu else "_cpu_smoke"
    return _emit(f"serving_prefix_prefill_hot_ttft_speedup{suffix}",
                 cold / hot, "x cold ttft", 1.0)


def bench_spec_decode():
    """ISSUE 15b: steady-state decode-STEP tokens/s, spec-on vs
    spec-off, on a repetitive workload the n-gram proposer can read.

    Two engines on one model ({spec k=3, off}); each pass admits the
    batch, prefills it, then takes the BEST per-step emission rate
    (emitted tokens / step wall) over every decode step — the PR-7
    min-over-steps discipline adapted to variable emission (spec steps
    emit 1..k+1 tokens).  Interleaved order-alternating passes; emits
    the spec lane with the spec-off lane as baseline, so
    vs_baseline > 1 is the speculative win."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config, \
        gpt2_124m_config
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    on_tpu = _on_tpu()
    cfg = (gpt2_124m_config(stacked_blocks=True) if on_tpu
           else gpt_test_config(stacked_blocks=True,
                                sequence_parallel=False))
    batch, new = (8, 64) if on_tpu else (4, 24)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    # repetitive prompts (a short pattern repeated): prompt lookup finds
    # the continuation, and tiny-GPT greedy decode cycles — both give
    # the verifier real multi-token accepts
    prompts = []
    for _ in range(batch):
        pat = rng.randint(0, cfg.vocab_size, (4,)).astype("int32")
        prompts.append(np.concatenate([pat] * 4))
    sp = SamplingParams(max_new_tokens=new)
    engines = {}
    for k in (3, 0):
        eng = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=batch, speculative_tokens=k))
        eng.generate(prompts, sp)          # warmup: compiles every program
        engines[k] = eng

    def best_step_tps(eng):
        rids = [eng.add_request(p, sp) for p in prompts]
        try:
            while any(not eng._requests[r].prefill_done for r in rids):
                eng.step()
            best = 0.0
            while eng.has_unfinished():
                before = sum(len(eng._requests[r].output_ids)
                             for r in rids)
                t0 = time.perf_counter()
                eng.step()
                dt = time.perf_counter() - t0
                emitted = sum(len(eng._requests[r].output_ids)
                              for r in rids) - before
                if emitted:
                    best = max(best, emitted / dt)
            return best
        finally:
            for r in rids:
                eng.release_request(r)

    reps = 3 if on_tpu else 4
    best = {k: 0.0 for k in engines}
    for i in range(reps):
        order = (3, 0) if i % 2 == 0 else (0, 3)
        for k in order:
            best[k] = max(best[k], best_step_tps(engines[k]))
    assert engines[3]._spec_accepted_total > 0, "no drafts accepted"
    suffix = "" if on_tpu else "_cpu_smoke"
    return _emit(f"serving_spec_decode_step_tokens_per_sec{suffix}",
                 best[3], "tokens/sec", best[0])


def bench_kernel_count():
    """ISSUE 12: launch-accounting + goodput/padding lane.  Boots the
    default (ragged) serving engine, reads `serving/kernels_per_step` —
    the number of separate compiled programs one decode step dispatches,
    the mega-kernel PR's (ROADMAP item 4) before/after number — and the
    padded-row fraction of the fixed-shape decode program at a known
    5-live-of-8 composition.  Asserts in-lane that the kernel count AND
    the `jit/recompile_cause{fn=serving:*}` series stay FLAT across a
    3→5 batch crossing (the ragged acceptance invariant), then emits
    both to BENCH_HISTORY.jsonl.  Metric names carry "overhead" so the
    history gate treats them lower-is-better: the mega-kernel PR
    dropping programs-per-step from 2 to 1 passes; a refactor that
    sneaks a third dispatch into the decode loop fails."""
    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config, \
        gpt2_124m_config
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    on_tpu = _on_tpu()
    monitor.enable(True)
    cfg = (gpt2_124m_config(stacked_blocks=True) if on_tpu
           else gpt_test_config(stacked_blocks=True,
                                sequence_parallel=False))
    prompt, new = (128, 16) if on_tpu else (8, 4)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (prompt,)).astype("int32")
               for _ in range(5)]
    sp = SamplingParams(max_new_tokens=new)
    eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=8))
    kern = monitor.gauge("serving/kernels_per_step")
    cause = monitor.counter("jit/recompile_cause")

    def serving_causes():
        snap = cause.snapshot()
        if not isinstance(snap, dict):
            return 0.0
        return sum(v for k, v in sorted(snap.items()) if "serving:" in k)

    eng.generate(prompts[:3], sp)           # warm: 3 running rows
    k3, c3 = kern.value, serving_causes()
    # deterministic padding read: admit all 5 (crossing the old bucket
    # boundary), prefill them, then read the gauges off ONE full decode
    # step — same-length prompts, so no fresh prefill programs muddy the
    # cause count
    rids = [eng.add_request(p, sp) for p in prompts]
    try:
        while any(not eng._requests[r].prefill_done for r in rids):
            eng.step()
        eng.step()                          # one 5-live decode step
        pad = monitor.gauge(
            "serving/padding_waste").labels(kind="rows").value
        k5, c5 = kern.value, serving_causes()
        while eng.has_unfinished():
            eng.step()
    finally:
        for r in rids:
            eng.release_request(r)
    assert k5 == k3 and k5 > 0, (k3, k5)
    assert c5 == c3, (c3, c5)
    suffix = "" if on_tpu else "_cpu_smoke"
    _emit(f"serving_decode_kernels_per_step_overhead{suffix}",
          k5, "programs/step", 1.0)
    return _emit(f"serving_decode_padding_overhead_frac{suffix}",
                 pad, "padded-row fraction", 1.0)


def bench_hybrid8_memfit():
    """BASELINE.md config 5 AXIS-MIX capacity check (sharding2 x pp2 x
    mp2 = 8 devices) at GPT-3 1.3B shapes: compile the full-shape hybrid
    training step on an 8-virtual-device CPU mesh and report XLA's
    per-device memory analysis against the v5e's 16 GiB HBM. Chip-free
    (compile only, never executed): vs_baseline >= 1.0 means the
    partitioned program fits the slice with headroom. bf16 AdamW moments
    (multi_precision=False) per the 1.3B single-chip recipe.
    1.3B rather than 6.7B shapes: this host's XLA-CPU moves big host
    buffers at ~25-50 MB/s (broadcast slow path), so every full-shape
    6.7B construction/placement pass costs ~20 min and the config blows
    any reasonable ladder budget (measured) — 6.7B
    hybrid MECHANICS stay covered by __graft_entry__ dryrun E. (A
    dp2-extended 16-device variant of this compile trips an XLA-CPU
    internal check at full shape; same note.)"""
    if os.environ.get("PTPU_MEMFIT_CHILD") != "1":
        # full-shape compile needs an 8-device CPU mesh pinned BEFORE any
        # jax import — re-exec with the env forced
        env = dict(os.environ)
        env.update(PTPU_MEMFIT_CHILD="1", PTPU_FORCE_PLATFORM="cpu",
                   # keep the layer stack as a rolled scan: the default
                   # policy fully unrolls depths <= 32 (a single-chip
                   # throughput trick), which makes this capacity
                   # compile far larger than it needs to be
                   PTPU_SCAN_UNROLL="1")
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
        env.pop("JAX_PLATFORMS", None)
        proc = subprocess.run(
            [sys.executable, __file__, "--config", "hybrid8_memfit"],
            env=env, capture_output=True, text=True, timeout=2900)
        sys.stdout.write(proc.stdout)
        if proc.returncode != 0:
            raise RuntimeError(proc.stderr[-1500:])
        return
    import paddle_tpu as paddle
    from paddle_tpu import jit, optimizer, parallel
    from paddle_tpu.models import (GPTForCausalLM, GPTPretrainingCriterion,
                                   gpt3_1p3b_config)

    # gpipe (scan-based pipeline): the 1F1B fused schedule's interleaved
    # HLO is too large to optimize within the budget on this host's single
    # core; gpipe's rolled scan keeps the program compact while exercising
    # the same shardings and full weight/activation shapes
    cfg = gpt3_1p3b_config(stacked_blocks=True, pp_num_microbatches=2,
                           recompute=True)
    paddle.seed(0)
    parallel.init_mesh(sharding=2, pp=2, mp=2)
    # capacity analysis only — zero-init the params through NUMPY buffers
    # (threefry-sampling GBs of normals on one CPU core dominates the
    # budget, and XLA-CPU's jnp.zeros broadcast writes at ~50 MB/s where
    # np.zeros + device_put is memcpy-speed) and construct natively in
    # bf16 so no transient fp32 copy of the full model exists
    from paddle_tpu.nn import initializer as _init
    import jax.numpy as _jnp
    import numpy as _np
    from paddle_tpu.core.dtype import convert_dtype as _cd
    _init.Normal.__call__ = lambda self, shape, dtype: _jnp.asarray(
        _np.zeros(shape, _cd(dtype)))
    paddle.set_default_dtype("bfloat16")

    def _mark(msg):
        print(f"memfit[{time.strftime('%H:%M:%S')}]: {msg}",
              file=sys.stderr, flush=True)

    _mark("mesh up, constructing model (bf16)...")
    model = GPTForCausalLM(cfg)
    _mark("constructed; placing on mesh...")
    model = parallel.place_model(model)
    model.bfloat16()        # cheap no-op pass for stragglers (fp32 inits)
    _mark("model ready, tracing...")
    crit = GPTPretrainingCriterion(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters(),
                          multi_precision=False)

    def step(x, y):
        loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = jit.compile(step, models=[model], optimizers=[opt])
    batch, seq = 8, 2048
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    lab = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32"))
    lowered = compiled.lower(ids, lab)
    print("memfit: lowered, compiling...", file=sys.stderr, flush=True)
    mem = lowered.compile().memory_analysis()
    per_dev_gb = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                  - mem.alias_size_in_bytes) / 2**30
    hbm_gb = 16.0
    return _emit("gpt3_1p3b_hybrid8_hbm_headroom",
                 round(hbm_gb / max(per_dev_gb, 1e-9), 4), "x (16GiB/use)",
                 1.0)


def bench_trace_overhead():
    """Observability tax gate (ISSUE 5, extended by ISSUE 6 to the perf
    hooks, ISSUE 11 to the cross-process trace-propagation hooks —
    inject/extract and the rpc header attach share the disabled-path
    budget — ISSUE 12 to the launch-accounting/goodput hooks: the
    engine decode step's per-dispatch launch-set bookkeeping and the
    kernels/padding/goodput gauge writes, whose disabled cost is one
    monitor-gate read; the HLO capture and recompile explainer run only
    at compile time and add nothing per step — and ISSUE 13 to the
    training-microscope per-step hooks: the StepGuard loss-spike EWMA
    observe + step-time gauge, the hapi goodput meter's wait/step
    accounting, the optimizer's lazy grad-norm cell store, and the
    PTPU_TRAIN_STATS gate read guarding the sampled per-layer
    reduction; the divergence forensics scan runs only on the bad-step
    path and the per-layer reduction only on sampled opt-in steps, so
    neither belongs in the per-step tax — and ISSUE 16 to the
    request-plane hooks: the engine step's slo.maybe_tick + reqlog gate
    reads (one module-global read each when off), the exemplar-stamping
    observe(v, trace_id=) signature on the latency histograms, the
    tail-sampling keep decision at root-span end, and — in the enabled
    measurement, with reqlog + exemplars + a zero tail budget flipped
    on — the wide-event build+emit charged EVERY step (conservative:
    real traffic releases at most one request per step) — and ISSUE 18
    to the chaos choke points: the rpc transport consults the net-fault
    plan at dial, send and recv on EVERY call, so all three
    ``faults.net_fire`` probes ride the per-step sequence; with
    PTPU_FAULTS unset each is one module-global read returning None —
    and ISSUE 20 to the memory-microscope hooks: the KV block-lifecycle
    counters ride the allocator hot paths unconditionally (disabled cost
    = one module-global read per event), and with PTPU_MEMOBS on the
    engine step adds one HBM/host timeline sample (TTL-cached RSS), the
    eviction-storm EWMA observe, and the interval-limited /kv snapshot
    publish fast path; the snapshot build itself runs at most 2Hz and
    the pressure forensics only on the failure path, so neither belongs
    in the per-step tax):
    what the
    monitor+trace+perf layers add to a train step, off vs on, asserting
    disabled overhead < 1% and enabled overhead < 5% of the step.  "Enabled" means monitor+trace; PTPU_PERF stays off in both
    measurements — perf mode deliberately syncs every timed call (MFU
    from async dispatch times would be fiction), so it is a diagnostic
    mode outside the always-on tax envelope, but its DISABLED cost (the
    gate reads and dead-branch guards in jit dispatch, the engine decode
    segments, and the hapi segment contexts) is part of both bounds here.

    Method: the per-step instrumentation sequence — the span wrapper plus
    the jit layer's enabled-mode telemetry (arg-signature cache probe,
    optimizer counter/gauge) — is timed DIRECTLY at high repetition and
    ratioed against the compiled step's measured floor.  An A/B of two
    full step loops cannot resolve this: the effect is µs-scale, and on a
    shared host the ms-scale step wobbles several percent even at
    min-of-N (measured; medians of paired diffs drift too).  The direct
    measurement is deterministic, and the ratio against the *floor* step
    time is the conservative reading (any real step is slower, making
    the true percentage smaller)."""
    import paddle_tpu as paddle  # noqa: F401 (backend pinned via import)
    from paddle_tpu import jit as pjit
    from paddle_tpu import monitor
    from paddle_tpu.models import gpt_test_config
    from paddle_tpu.resilience import faults as mfaults

    mtrace = monitor.trace
    mperf = monitor.perf
    mreqlog = monitor.reqlog
    mslo = monitor.slo
    on_tpu = _on_tpu()
    cfg = gpt_test_config(num_hidden_layers=2, stacked_blocks=True)
    batch, seq = (8, 128) if on_tpu else (4, 32)
    compiled, args, _ = _gpt_step(cfg, batch, seq)
    float(compiled(*args))   # warmup: compile + page-in
    t_step = float("inf")
    for _ in range(40):
        t0 = time.perf_counter()
        float(compiled(*args))
        t_step = min(t_step, time.perf_counter() - t0)

    a_args = tuple(t._data for t in args)
    seen = {f"nstate=0;{pjit._arg_signature((a_args, {}))}"}
    # cached handles, matching the engine's __init__-cached gauges
    m_kern = monitor.gauge("bench/kernels_per_step")
    m_pad = monitor.gauge("bench/padding_waste")
    m_pad_r = m_pad.labels(kind="rows")
    m_pad_t = m_pad.labels(kind="tokens")
    m_good = monitor.gauge("bench/goodput_tokens_per_s")
    # ISSUE 13 training-microscope per-step objects, constructed once
    # like StepGuard/Model.fit construct theirs
    mtrain = monitor.train
    spike = mtrain.LossSpikeDetector()
    meter = mtrain.GoodputMeter()
    m_step_t = monitor.gauge("bench/step_time")
    grad_cell = [None]
    fake_grads = [a_args[0]]   # the lazy grad-norm CELL STORE (the
    # reduction itself runs at scrape time, off the per-step path)
    # ISSUE 16: the engine's __init__-cached latency histogram, observed
    # with the exemplar-stamping signature every step
    m_lat = monitor.histogram("bench/ttft")
    # ISSUE 20 memory-microscope per-step objects, constructed once like
    # BlockKVCache/LLMEngine construct theirs: the lifecycle-event
    # ledger, the storm detector, and a real (tiny) pool for the
    # interval-amortized /kv snapshot build
    mmem = monitor.memory
    acct = mmem.KVAccounting()
    storm = mmem.StormDetector()
    kv_pool = __import__(
        "paddle_tpu.serving.kv_cache", fromlist=["BlockKVCache"]
    ).BlockKVCache(1, 8, 4, 1, 2)

    def instr(i):
        # exactly what one instrumented step adds on top of the math:
        # the caller's span, plus CompiledFunction.__call__'s telemetry
        # — signature probe of the real args + steps counter + lr gauge,
        # behind the same enabled() gates the real code path carries —
        # plus the ISSUE-6 perf hooks' gate reads: the jit dispatch
        # guard, the engine decode-segment guards, and the hapi train
        # path's three segment contexts (all dead branches with perf off)
        # — plus the ISSUE-11 propagation hooks: the rpc client's header
        # attach (inject) and the rpc server's header parse (extract),
        # both one-global-read None paths when tracing is off — plus
        # the ISSUE-13 training hooks (see the docstring)
        with mtrace.span("bench/train_step", step=i):
            hdr = mtrace.inject()           # rpc _call header attach
            _ctx = mtrace.extract(hdr)      # rpc _handle header parse
            # ISSUE 18: the rpc transport's chaos probes — dial, send,
            # recv each consult the net-fault plan per call; disabled
            # (no PTPU_FAULTS) each is one global read -> None
            _f = mfaults.net_fire(site="rpc.dial", peer="bench",
                                  kinds=("net_drop", "net_delay",
                                         "net_partition"))
            _f = mfaults.net_fire(site="rpc.send", peer="bench")
            _f = mfaults.net_fire(site="rpc.recv", peer="bench")
            perf_on = mperf.enabled()
            if monitor.enabled() or mtrace.enabled() or perf_on:
                sig = f"nstate=0;{pjit._arg_signature((a_args, {}))}"
                if sig not in seen:
                    seen.add(sig)
            # ISSUE 13: the sampled per-layer reduction's disabled path
            # is exactly this one module-global read in the optimizer
            _stats_on = mtrain.enabled()
            if monitor.enabled():
                monitor.counter("optimizer/steps").inc()
                monitor.gauge("optimizer/lr").set(1e-4)
                # ISSUE 12 launch accounting + goodput, the engine
                # decode step's per-step sequence: build the launch set,
                # record two dispatches, write the four gauges
                launches = set()
                launches.add(("ragged", 8, 1))
                launches.add(("sample", 8))
                m_kern.set(len(launches))
                m_pad_r.set(0.375)
                m_pad_t.set(0.375)
                m_good.set(1234.5)
                # ISSUE 13 per-step training sequence: StepGuard's
                # step-time gauge + EWMA loss-spike observe, the hapi
                # goodput meter's wait/step accounting, and the lazy
                # grad-norm cell store (every step here; the real
                # optimizer samples it every _GRADNORM_EVERY steps)
                t0s = time.perf_counter()
                m_step_t.set(time.perf_counter() - t0s)
                spike.observe(0.5 + i * 1e-9, step=i)
                meter.wait(1e-7)
                meter.step(1e-6, examples=8)
                grad_cell[0] = list(fake_grads)
                # ISSUE 16: exemplar-stamping observe (the engine's
                # _record_latency signature; stamps only with
                # PTPU_EXEMPLARS on, kwarg-pass + gate read otherwise)
                m_lat.observe(1e-4, trace_id="bench-trace")
            # ISSUE 20 memory-microscope per-step sequence.  The block-
            # lifecycle counters ride the cache hot paths unconditionally
            # (the gate is inside KVAccounting.on), so their disabled
            # cost — one module-global read each — belongs in BOTH
            # bounds; a decode step touches the allocator at most a few
            # times (one alloc per block boundary per row), so two
            # events is the conservative per-step charge.  With
            # PTPU_MEMOBS on, the engine additionally takes one timeline
            # sample (host RSS is TTL-cached: a dict read most steps),
            # feeds the eviction-storm EWMA, and offers the /kv snapshot
            # publish (interval-limited to 2Hz: one monotonic read on
            # the fast path; the O(num_blocks) build amortizes outside
            # the per-step tax)
            acct.on("alloc")
            acct.on("free")
            if mmem.enabled():
                mmem.sample(hbm_peak=None, hbm_in_use=1 << 20,
                            host_rss=mmem.host_rss_bytes())
                storm.observe(0)
                mmem.maybe_publish_kv(
                    lambda: mmem.build_kv_snapshot(kv_pool, []))
            # ISSUE 16 engine-step hooks: slo tick + reqlog emit gate
            # (one module-global read each when off); with reqlog on,
            # the release-time wide-event build+emit charged every step
            mslo.maybe_tick()
            if mreqlog.enabled():
                mreqlog.emit(mreqlog.event(
                    i, trace_id="bench-trace", ttft_s=1e-4,
                    generated_tokens=8))
            t0 = time.perf_counter() if perf_on else 0.0   # jit hook
            _ = time.perf_counter() if perf_on else 0.0    # decode segs
            with mperf.segment("bench", "forward"):
                pass
            with mperf.segment("bench", "backward"):
                pass
            with mperf.segment("bench", "optimizer"):
                pass
            del t0, _ctx, _stats_on, _f

    def per_call(n):
        t0 = time.perf_counter()
        for i in range(n):
            instr(i)
        return (time.perf_counter() - t0) / n

    prev_mon, prev_trace = monitor.enabled(), mtrace.enabled()
    prev_perf = mperf.enabled()
    prev_rl, prev_ex = mreqlog.enabled(), monitor.exemplars_enabled()
    prev_tail = mtrace.tail_budget()
    prev_mem = mmem.enabled()
    try:
        mperf.enable(False)   # perf is a synced diagnostic mode: its
        # disabled cost gates here, its enabled cost is the point of it
        monitor.enable(False)
        mtrace.enable(False)
        mreqlog.enable(False)
        monitor.enable_exemplars(False)
        mtrace.set_tail_budget(None)
        mmem.enable(False)
        c_off = min(per_call(20_000) for _ in range(3))
        monitor.enable(True)
        mtrace.enable(True)
        # ISSUE 20: the memory microscope rides the enabled measurement
        mmem.enable(True)
        # ISSUE 16 wings on: ring-only reqlog, exemplar stamping, and a
        # zero tail budget (every boring root pays the keep decision AND
        # the drop — the most expensive sampling path)
        mreqlog.enable(True)
        monitor.enable_exemplars(True)
        mtrace.set_tail_budget(0)
        c_on = min(per_call(5_000) for _ in range(3))
    finally:
        monitor.enable(prev_mon)
        mtrace.enable(prev_trace)
        mperf.enable(prev_perf)
        mreqlog.enable(prev_rl)
        monitor.enable_exemplars(prev_ex)
        mtrace.set_tail_budget(prev_tail)
        mmem.enable(prev_mem)
        mreqlog.reset()
        mmem.reset()
    off_pct = c_off / t_step * 100.0
    on_pct = c_on / t_step * 100.0
    assert off_pct < 1.0, (
        f"disabled monitor+trace costs {c_off*1e9:.0f}ns/step = "
        f"{off_pct:.3f}% of a {t_step*1e6:.0f}us step (>1%)")
    assert on_pct < 5.0, (
        f"enabled monitor+trace costs {c_on*1e6:.1f}us/step = "
        f"{on_pct:.3f}% of a {t_step*1e6:.0f}us step (>5%)")
    print(f"trace_overhead: step floor {t_step*1e6:.0f}us; "
          f"disabled +{c_off*1e9:.0f}ns ({off_pct:.4f}%), "
          f"enabled +{c_on*1e6:.2f}us ({on_pct:.4f}%)", file=sys.stderr)
    return _emit("train_step_trace_overhead_enabled_pct", on_pct,
                 "% of step", 5.0)


def bench_router_fanout():
    """ISSUE 17: router dispatch/absorb throughput over fake in-process
    replicas — the pure host-side cost of the multi-replica tier (sticky
    signature hashing, affinity-LRU lookup, least-loaded scoring, frame
    build, absorb) with the engine and rpc taken out of the loop.

    Workload: 512 requests in 8 shared-prefix families (48-token prefix
    + distinct 16-token tails) across 4 echo replicas that complete
    everything on their next poll, so the wall is submit + two router
    pump cycles.  Self-asserts in-lane that affinity actually routed
    (every non-first family member is a sticky hit) — a throughput
    number from a router that silently fell back to least-loaded would
    gate the wrong thing.  Emits best-of-reps requests/s; the router is
    backend-free, so the CPU lane is the real lane, but the metric keeps
    the smoke suffix off-TPU so shared-host noise gates at the loose
    fast-lane tolerance."""
    import random

    from paddle_tpu import monitor
    from paddle_tpu.serving.router import (Router, RouterConfig,
                                           poll_frame, result_frame)
    from paddle_tpu.serving.scheduler import SamplingParams

    BS, FAMILIES, REQS = 16, 8, 512

    class _EchoReplica:
        """Accepts every frame, completes it all on the next poll."""
        role = "both"

        def __init__(self, name):
            self.name = name
            self._pending = []

        def submit(self, frame):
            self._pending.append(frame)
            return True

        submit_handoff = submit

        def poll(self):
            done = [result_frame(f["rid"], self.name, ok=True,
                                 token_ids=[0], finish_reason="stop")
                    for f in self._pending]
            self._pending = []
            return poll_frame(self.name, False, done, [], [])

    replicas = [_EchoReplica(f"r{i}") for i in range(4)]
    snap = {r.name: {"state": "healthy"} for r in replicas}
    rng = random.Random(0)
    prefixes = [[rng.randrange(1, 128) for _ in range(48)]
                for _ in range(FAMILIES)]
    prompts = [prefixes[i % FAMILIES]
               + [rng.randrange(1, 128) for _ in range(16)]
               for i in range(REQS)]
    params = SamplingParams(max_new_tokens=8)
    cfg = RouterConfig(sticky=True, disaggregate=False, affinity_cap=4096,
                       resubmit_limit=1, block_size=BS)

    def run_once():
        router = Router(replicas, lambda: snap, cfg)
        t0 = time.perf_counter()
        rids = [router.submit(p, params) for p in prompts]
        while router.pending():
            router.poll()
        dt = time.perf_counter() - t0
        for rid in rids:
            router.release(rid)
        return REQS / dt

    prev_mon = monitor.enabled()
    monitor.enable(True)             # the sticky self-assert reads counters
    try:
        run_once()                   # warmup (imports, counter creation)
        hits0 = monitor.counter("router/sticky_hits").value
        best = max(run_once() for _ in range(5))
        hits = monitor.counter("router/sticky_hits").value - hits0
        assert hits >= 5 * (REQS - FAMILIES), (
            f"sticky routing fell back to least-loaded: {hits} affinity "
            f"hits over 5 reps, expected >= {5 * (REQS - FAMILIES)}")
    finally:
        monitor.enable(prev_mon)
    suffix = "" if _on_tpu() else "_cpu_smoke"
    return _emit(f"router_fanout_requests_per_sec{suffix}", best,
                 "requests/sec", 5000.0)


def bench_serving_load():
    """ISSUE 19: the serving closed loop, measured through the REAL HTTP
    front door — an ApiServer over a small engine, driven by seeded
    OPEN-LOOP arrivals (the schedule never waits on completions, so
    queueing shows up as TTFT, not as reduced offered load) at rising
    QPS with mixed prompt lengths, every request SSE-streamed so TTFT is
    first-chunk wall time off a live socket.

    Emits goodput (requests' completed tokens per wall second —
    higher-is-better, the gated lane) and TTFT p50/p95/p99 + TPOT p95
    (named *_overhead_* so history mode gates them lower-is-better).
    Self-asserts in-lane that every stream finished "stop" and none
    errored — a latency number from a run that shed or hung streams
    would gate the wrong thing."""
    import json as _json
    import threading
    import urllib.request

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config
    from paddle_tpu.serving import (ApiServer, EngineConfig, LLMEngine,
                                    SamplingParams)

    LENS = (4, 6, 8)
    STAGES = ((4.0, 16), (8.0, 24), (16.0, 32))   # (qps, requests)
    NEW = 8

    paddle.seed(0)
    cfg = gpt_test_config(stacked_blocks=True, sequence_parallel=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    engine = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=8))
    rng = np.random.RandomState(0)
    # warm every prompt-length's prefill program + the decode/sampler
    # path BEFORE the clock runs: this lane measures serving, not XLA
    engine.generate(
        [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
         for n in LENS], SamplingParams(max_new_tokens=2))
    server = ApiServer(engine=engine, poll_s=0.002)

    results, lock = [], threading.Lock()

    def fire(ids):
        body = _json.dumps({"prompt": ids, "max_tokens": NEW,
                            "stream": True}).encode()
        t_start = time.perf_counter()
        try:
            resp = urllib.request.urlopen(urllib.request.Request(
                server.url + "/v1/completions", data=body,
                headers={"Content-Type": "application/json"}),
                timeout=120)
            first = last = None
            ntok, reason = 0, None
            for raw in resp:
                line = raw.decode("utf-8").strip()
                if not line.startswith("data: ") or line == "data: [DONE]":
                    continue
                now = time.perf_counter()
                choice = _json.loads(line[len("data: "):])["choices"][0]
                k = len(choice.get("token_ids") or [])
                if k:
                    if first is None:
                        first = now
                    last = now
                    ntok += k
                reason = choice.get("finish_reason") or reason
            rec = {"ttft": first - t_start, "ntok": ntok,
                   "reason": reason,
                   "tpot": ((last - first) / (ntok - 1)
                            if ntok > 1 else None)}
        except Exception as e:   # recorded, then failed loudly in-lane
            rec = {"error": repr(e)}
        with lock:
            results.append(rec)

    threads = []
    t_wall = time.perf_counter()
    t_next = t_wall
    for qps, n in STAGES:
        for _ in range(n):
            t_next += float(rng.exponential(1.0 / qps))
            ids = [int(t) for t in rng.randint(
                0, cfg.vocab_size, (int(LENS[rng.randint(len(LENS))]),))]
            wait = t_next - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            th = threading.Thread(target=fire, args=(ids,), daemon=True)
            th.start()
            threads.append(th)
    for th in threads:
        th.join(timeout=240)
    wall = time.perf_counter() - t_wall
    server.stop()

    total = sum(n for _, n in STAGES)
    assert len(results) == total and all(
        not th.is_alive() for th in threads), "streams hung"
    errs = [r for r in results if "error" in r]
    assert not errs, errs[:3]
    assert all(r["reason"] == "stop" and r["ntok"] == NEW
               for r in results), results[:3]
    ttfts = np.array([r["ttft"] for r in results]) * 1e3
    tpots = np.array([r["tpot"] for r in results
                      if r["tpot"] is not None]) * 1e3
    goodput = (NEW * total) / wall
    suffix = "" if _on_tpu() else "_cpu_smoke"
    _emit(f"serving_load_ttft_p50_overhead_ms{suffix}",
          float(np.percentile(ttfts, 50)), "ms", 20.0)
    _emit(f"serving_load_ttft_p95_overhead_ms{suffix}",
          float(np.percentile(ttfts, 95)), "ms", 60.0)
    _emit(f"serving_load_ttft_p99_overhead_ms{suffix}",
          float(np.percentile(ttfts, 99)), "ms", 100.0)
    _emit(f"serving_load_tpot_p95_overhead_ms{suffix}",
          float(np.percentile(tpots, 95)), "ms", 10.0)
    return _emit(f"serving_load_goodput_tokens_per_sec{suffix}",
                 goodput, "tokens/sec", 200.0)


LADDER = {
    "gpt124m": bench_gpt124m,
    "resnet50": bench_resnet50,
    "bert_base": bench_bert_base,
    "gpt3_1p3b": bench_gpt3_1p3b,
    "gpt124m_decode": bench_decode,
    "lowbit_kv_decode": bench_lowbit_kv_decode,
    "prefix_prefill": bench_prefix_prefill,
    "spec_decode": bench_spec_decode,
    "kernel_count": bench_kernel_count,
    "router_fanout": bench_router_fanout,
    "serving_load": bench_serving_load,
    "trace_overhead": bench_trace_overhead,
    "hybrid8_memfit": bench_hybrid8_memfit,
}


def main():
    argv = sys.argv[1:]
    if argv and argv[0] == "--ladder":
        results = []
        for name in LADDER:
            entry = None
            try:
                proc = subprocess.run(
                    [sys.executable, __file__, "--config", name],
                    capture_output=True, text=True,
                    timeout=3000 if name.endswith("memfit") else 1200)
                for ln in proc.stdout.splitlines():
                    try:
                        entry = json.loads(ln)
                    except ValueError:
                        continue
                if entry is None or proc.returncode != 0:
                    # crashed / OOM: record the failure, not a stale line
                    entry = {"metric": name, "error":
                             f"rc={proc.returncode}",
                             "tail": proc.stderr.strip()[-400:]}
            except subprocess.TimeoutExpired:
                entry = {"metric": name, "error": "timeout"}
            results.append(entry)
            with open("BENCH_LADDER.json", "w") as f:  # survive later crashes
                json.dump(results, f, indent=1)
        for r in results:
            print(json.dumps(r))
        failed = [r["metric"] for r in results if "error" in r]
        if failed:
            sys.exit(f"bench --ladder: {len(failed)} config(s) failed: "
                     + ", ".join(failed))
        return
    # the ladder parent above stays off jax (its children need the chip)
    from paddle_tpu.jit import enable_compile_cache

    enable_compile_cache()   # before the first trace
    if argv and argv[0] == "--config":
        LADDER[argv[1]]()
        return
    # driver path: headline only, ONE line
    bench_gpt124m()


if __name__ == "__main__":
    main()
