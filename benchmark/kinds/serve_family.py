"""kind `serve_family`: the closed loop of kind `serve` (its `send_one`,
`_Client`, `_failed` and `_monitor_delta`, imported) around an `LLMEngine`
whose model, configuration object and plain reference are named by the
configuration file's `harness` (lib/family.py).  Another architecture adds
a configuration file and a reference module, and no runner.

Traffic parameters (benchmark/traffic/<name>.json): as kind `serve` -
clients, prompt_len and max_tokens as [[value, count], ..], engine (the
keyword arguments of `EngineConfig`), warmup_s, trace_s, check_requests.
Token ids are uniform over the configuration's `vocab_size` (a sliced
vocabulary is a smaller vocabulary), no shared prefix, greedy.

Set-up, window, attempted, failed and the latencies are kind `serve`'s.

correct: no failed request; nothing compiled inside the window; prefill
took the flash kernel and decode the ragged kernel, with no fallback other
than `ragged_fallback:chunk_gt_1`; no routed (token, expert) pair was
dropped (the program's `serving/moe_pairs`, held here plus absent, count
`top_k` pairs for every token `serving/moe_tokens` counts); and for `check_requests`
finished requests, chosen from the seed, every served token's logit in the
reference's full forward over prompt + served tokens lies within
LOGIT_MARGIN of that position's largest logit, and at least
MIN_SHARE_WITHIN of them within NEAR of it.  The pools are freed before
the reference runs: at 8,448 positions it does not fit beside them.
"""
import random
import threading
import time

from benchmark.kinds.serve import (ALLOWED_FALLBACKS, SOCKET_TIMEOUT_S,
                                   _brief, _Client, _failed, _monitor_delta,
                                   send_one)
from benchmark.lib import family, host_phases, stats
from benchmark.lib.common import Laps, fold_seed, log, profiler_slice
from benchmark.lib.traffic import Requests

# Two limits on how far a served token's float32 reference logit lies under
# that position's largest (logits of these configurations have a standard
# deviation near 1.1: a unit-RMS final norm against a head of N(0, 0.02)
# over 3072 inputs).  The engine computes in bfloat16.  Nearly every
# position differs from the reference by rounding (0.01 rms a logit), but
# with random weights the router's 4th and 5th scores are often closer than
# that, so about one position in a hundred sends a token to another expert
# than the reference does and reads 0.2-0.35 rms.  So the worst token of a
# correct run reads tenths, not hundredths (0.42-0.97 over eight runs of
# 512-768 tokens), and a fault that moves EVERY token a little (a lower
# precision, a missing window mask, a dropped expert) shows less in the
# worst token than in how many tokens stray.  LOGIT_MARGIN bounds the worst
# token at twice the largest correct reading: it catches what breaks a
# token outright (a wrong block read 1.97 over 96 tokens, unwritten rows of
# the grouped product 3.48).  MIN_SHARE_WITHIN bounds the share of served
# tokens within NEAR of their position's largest logit: correct runs read
# 0.9922-0.9974, the reference computed in float8 - the precision below
# the bfloat16 stated - 0.862 against the same tokens, a missing window
# mask 0.896, a dropped expert 0.9375 (PERF.md, section 6, PR 28).
LOGIT_MARGIN = 2.0
NEAR = 0.25
MIN_SHARE_WITHIN = 0.95


def _check_against_reference(model, config, records, traffic, seed):
    """(worst margin, share of served tokens within NEAR) over
    `check_requests` finished requests; one sequence at a time, each
    padded to one length so the reference compiles once (causal: padding
    at the end changes no earlier position).  BENCH_REFERENCE_FAULT=<name>
    also logs both under that deliberate fault of the reference (a reading
    for PERF.md; it decides nothing)."""
    import os

    import numpy as np

    done = [r for r in records if not _failed(r)]
    picked = random.Random(fold_seed(seed)).sample(
        done, min(int(traffic["check_requests"]), len(done)))
    if not picked:
        return None, None
    longest = (max(v for v, _ in traffic["prompt_len"])
               + max(v for v, _ in traffic["max_tokens"]))
    width = -(-longest // 128) * 128
    ids = np.zeros((len(picked), width), np.int32)
    for row, r in zip(ids, picked):
        seq = r["prompt"] + r["tokens"]
        row[:len(seq)] = seq
    ref = family.reference(config)
    params = ref.params_from_model(model)

    def served(fault):
        kw = {"fault": fault} if fault else {}
        margins, spread = ref.greedy_margins(params, ids, config, **kw)
        cut = [(len(r["prompt"]) - 1, len(r["prompt"]) - 1
                + len(r["tokens"])) for r in picked]   # logits of token 0..
        m = np.concatenate([margins[i, lo:hi]
                            for i, (lo, hi) in enumerate(cut)])
        std = np.mean([spread[i, lo:hi].mean()
                       for i, (lo, hi) in enumerate(cut)])
        if not np.isfinite(m).all():
            return float("inf"), 0.0, std, m.size
        return float(m.max()), float((m <= NEAR).mean()), std, m.size

    worst, share, std, n = served(None)
    log(f"reference check on {len(picked)} requests (indices "
        f"{[r['index'] for r in picked]}, prompts "
        f"{[len(r['prompt']) for r in picked]}), {n} served tokens: worst "
        f"margin {worst:.4f} logits (limit {LOGIT_MARGIN}), share within "
        f"{NEAR} of the largest {share:.4f} (limit {MIN_SHARE_WITHIN}), "
        f"logit std {std:.3f}")
    fault = os.environ.get("BENCH_REFERENCE_FAULT")
    if fault:
        w, s, _, _ = served(fault)
        log(f"the same tokens under the reference with fault {fault!r}: "
            f"worst margin {w:.4f}, share within {NEAR} {s:.4f}")
    return worst, share


def _pairs_dropped(counters, cfg):
    """Routed pairs the program should have counted minus those it did,
    over the window: every token routes `num_experts_per_tok` pairs in
    every expert layer, each either multiplied here or absent.  None when
    the program counted no token."""
    def total(name):
        return sum(v for k, v in counters.items()
                   if k.startswith(name + "{"))

    tokens = total("serving/moe_tokens")
    if not tokens:
        return None
    return tokens * cfg.num_experts_per_tok - total("serving/moe_pairs")


def _error_body(host, port, prompt):
    """What the server answers a one-token request with (`send_one` keeps
    no body): the text of its error, for the exception's message."""
    import http.client
    import json

    conn = http.client.HTTPConnection(host, port, timeout=SOCKET_TIMEOUT_S)
    try:
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": prompt, "max_tokens": 1}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        return f"HTTP {resp.status} {resp.read()[:2000]!r}"
    except (OSError, http.client.HTTPException) as e:
        return repr(e)
    finally:
        conn.close()


def run(spec):
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.ops.pallas_ops import attention_path_counts
    from paddle_tpu.serving import EngineConfig, LLMEngine
    from paddle_tpu.serving.api import start_api_server

    tr = spec["traffic"]
    lap = Laps(spec["t0"])
    model, cfg = family.build_model(spec["config"], spec["seed"])
    model.eval()
    engine = LLMEngine(model, EngineConfig(**tr["engine"]))
    jax.block_until_ready([p._data for p in model.parameters()])
    lap("weights")
    server = start_api_server(engine=engine, port=0)
    shared = {"stop": threading.Event(), "lock": threading.Lock(),
              "next": 0,
              "requests": Requests(tr, cfg.vocab_size,
                                   fold_seed(spec["seed"]))}
    clients = []
    try:
        # one request of each prompt length, alone: prefill(len), the
        # one-row sampler, the ragged decode program and its sampler
        never = threading.Event()
        for i, n in enumerate(shared["requests"].prompt_lengths()):
            ids = random.Random(fold_seed(spec["seed"]) + i).choices(
                range(cfg.vocab_size), k=n)
            rec = send_one(server.host, server.port, -1 - i, ids, 4, never,
                           deadline_s=SOCKET_TIMEOUT_S - 30)
            if _failed(rec):
                raise RuntimeError(
                    f"warm-up request failed: {_brief(rec)}; the server "
                    f"says: {_error_body(server.host, server.port, ids)}")
        lap("compile_or_cache")
        clients = [_Client(shared, server.host, server.port)
                   for _ in range(int(tr["clients"]))]
        for c in clients:
            c.start()
        time.sleep(float(tr["warmup_s"]))
        lap("warmup")

        compiles_before = spec["compiles"].compiles
        snap_before = monitor.snapshot()
        t_begin = time.perf_counter()
        setup_s = t_begin - spec["t0"]
        time.sleep(spec["seconds"])
        t_end = time.perf_counter()
        snap_after = monitor.snapshot()
        compiles_in_window = spec["compiles"].compiles - compiles_before

        traced = {}
        if spec["trace"]:
            with profiler_slice(traced):
                time.sleep(float(tr["trace_s"]))
    finally:
        shared["stop"].set()
        for c in clients:
            c.join(timeout=60)
        server.stop()
    if any(c.is_alive() for c in clients):
        raise RuntimeError("a client thread did not stop")

    window_s = t_end - t_begin
    records = [r for c in clients for r in c.records]
    tokens_in = sum(t_begin <= t < t_end
                    for r in records for t in r["arrivals"])
    sent = [r for r in records if t_begin <= r["sent"] < t_end]
    ended = [r for r in sent
             if r["ended"] is not None and r["ended"] < t_end]
    failed = [r for r in ended if _failed(r)]
    live = [r for r in sent if not any(r is f for f in failed)]
    seen = [[t for t in r["arrivals"] if t < t_end] for r in live]
    end_to_end = {"serve_tokens_per_s": tokens_in / window_s,
                  "setup_s": setup_s}
    counters = _monitor_delta(snap_before, snap_after)
    timings = {"window_s": window_s, "requests": len(ended),
               "setup_split_s": lap.split,
               "compiles_in_window": compiles_in_window,
               "max_num_seqs": int(tr["engine"]["max_num_seqs"]),
               "kv_pool_blocks": {g: k.num_blocks
                                  for g, k in engine.caches.items()}}
    ttft = [(a[0] - r["sent"]) * 1e3 for a, r in zip(seen, live) if a]
    gaps = [g * 1e3 for g in stats.pooled_gaps(seen)]
    if ttft and gaps:
        end_to_end["ttft_p95_ms"], n_ttft = stats.percentile(ttft, 95)
        end_to_end["itl_p95_ms"], n_gaps = stats.percentile(gaps, 95)
        timings.update(ttft_median_ms=stats.median(ttft),
                       itl_median_ms=stats.median(gaps),
                       ttft_samples=n_ttft, itl_samples=n_gaps)
        log(f"{len(sent)} requests sent in {window_s:.2f} s, {len(ended)} "
            f"of them ended in it, {len(failed)} failed; {tokens_in} tokens "
            f"received; TTFT median {timings['ttft_median_ms']:.1f} p95 "
            f"{end_to_end['ttft_p95_ms']:.1f} ms over {n_ttft}; gap median "
            f"{timings['itl_median_ms']:.2f} p95 "
            f"{end_to_end['itl_p95_ms']:.2f} ms over {n_gaps}")
    for r in failed[:3]:
        log(f"failed request: {_brief(r)}")

    paths = attention_path_counts()
    fallbacks = {k: v for k, v in paths.items()
                 if "_fallback:" in k and k not in ALLOWED_FALLBACKS}
    dropped = _pairs_dropped(counters, cfg)
    # the reference at the longest sequence does not fit beside the pools
    for cache in engine.caches.values():
        cache.k_blocks = cache.v_blocks = None
    del engine
    t_ref = time.perf_counter()
    worst, share = _check_against_reference(model, spec["config"], records,
                                            tr, spec["seed"])
    lap.split["reference_after_window"] = time.perf_counter() - t_ref
    checks = {
        "requests_ok": bool(ended) and not failed,
        "no_compile_in_window": compiles_in_window == 0,
        "kernel_paths": any(k.startswith("attn_kernel") for k in paths)
        and paths.get("ragged_kernel", 0) >= 1 and not fallbacks,
        "no_pair_dropped": dropped == 0,
        "reference_margin": worst is not None and worst <= LOGIT_MARGIN,
        "reference_share_near": (share is not None
                                 and share >= MIN_SHARE_WITHIN),
    }
    phases = {k.split("phase=")[1].split("}")[0]: round(1e3 * v / max(
        host_phases.program_steps(counters), 1), 3)
        for k, v in counters.items()
        if k.startswith("serving/host_time{") and k.endswith(":sum")}
    log(f"host phases, ms a program step: {phases}")
    log(f"compiles in window {compiles_in_window}; attention paths {paths}; "
        f"routed pairs not counted {dropped}")
    log(f"set-up split (s): {lap}")
    return {"checks": checks, "attempted": len(ended),
            "failed": len(failed), "end_to_end": end_to_end,
            "timings": timings, "counters": counters, "traced": traced}
