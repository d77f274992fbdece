"""kind `serve_family_paths`: kind `serve_family`'s window, clients,
reference check and limits (imported, not copied: `_check_against_reference`,
`_pairs_dropped`, `_error_body`, LOGIT_MARGIN, NEAR, MIN_SHARE_WITHIN, and
through it kind `serve`'s `send_one`, `_Client`, `_failed`,
`_monitor_delta`), with the two checks that name one family's kernels or
mechanism decided from the configuration's data instead:

  kernel_paths     `harness.kernel_paths`: every name in `required` was
                   counted at least once by the program's gates
                   (`attention_path_counts`; a name also matches its
                   `name:variant` counts), and no `*_fallback:*` was
                   counted outside `allowed_fallbacks` and kind `serve`'s
                   `ALLOWED_FALLBACKS`.  Without the key: kind
                   `serve_family`'s rule (`attn_kernel`, `ragged_kernel`).
  no_pair_dropped  only where the configuration has `num_experts_per_tok`:
                   a model without routed experts has no pair to drop.

and the STATE pools freed beside the K/V pools before the reference runs;
and a traced slice that lasts `trace_s` and then until it holds a prefill
step, at most TRACE_EXTRA_S more (`sleep_through_a_prefill`).
A family whose layers are not attention (the first: brumby's power
retention, which calls neither `attn_kernel*` nor `ragged_kernel` and keeps
no K/V block) then adds a configuration file and a reference module, and no
runner.  `run` below is kind `serve_family`'s but for those lines: that
file belongs to the benchmark as accepted and a PR of another kind may not
edit it (PERF.md, section 7: fold the two).

Traffic parameters, set-up, window, attempted, failed, the latencies and
the rest of `correct`: kind `serve_family`'s.
"""
import random
import threading
import time

from benchmark.kinds.serve import (ALLOWED_FALLBACKS, SOCKET_TIMEOUT_S,
                                   _brief, _Client, _failed, _monitor_delta,
                                   send_one)
from benchmark.kinds.serve_family import (LOGIT_MARGIN, MIN_SHARE_WITHIN,
                                          _check_against_reference,
                                          _error_body, _pairs_dropped)
from benchmark.lib import family, host_phases, stats
from benchmark.lib.common import Laps, fold_seed, log, profiler_slice
from benchmark.lib.traffic import Requests

TRACE_EXTRA_S = 10.0     # what a traced slice may wait for a prefill step
DEFAULT_PATHS = {"required": ["attn_kernel", "ragged_kernel"],
                 "allowed_fallbacks": []}


def kernel_paths_ok(paths, harness):
    """`paths` ({name: times counted}) against `harness.kernel_paths`."""
    want = harness.get("kernel_paths", DEFAULT_PATHS)
    allowed = set(ALLOWED_FALLBACKS) | set(want.get("allowed_fallbacks", ()))
    took = all(any(k == name or k.startswith(name + ":")
                   for k, n in paths.items() if n)
               for name in want["required"])
    fell = [k for k, n in paths.items()
            if n and "_fallback:" in k and k not in allowed]
    return took and not fell


def family_checks(config, paths, dropped):
    """The checks that depend on what the family is made of."""
    out = {"kernel_paths": kernel_paths_ok(paths, config["harness"])}
    if "num_experts_per_tok" in config:
        out["no_pair_dropped"] = dropped == 0
    return out


def _prefill_steps(monitor):
    return _monitor_delta({}, monitor.snapshot()).get(
        "serving/step_time{phase=prefill}:count", 0)


def sleep_through_a_prefill(monitor, seconds, extra=TRACE_EXTRA_S):
    """The traced slice: `seconds`, and then, while no prefill step has
    been read back since it began, up to `extra` more.  With answers of
    one to two thousand tokens a request - and so a prefill - begins every
    few seconds, and a slice of 2 s would hold none in two runs of five:
    a reader of the prefill kernel would then find nothing to read."""
    began = _prefill_steps(monitor)
    time.sleep(seconds)
    deadline = time.perf_counter() + extra
    while (_prefill_steps(monitor) == began
           and time.perf_counter() < deadline):
        time.sleep(0.1)


def step_durations(monitor, phase="decode"):
    """{upper bound in seconds: steps} of `serving/step_time{phase}` so
    far, from the histogram's buckets: the mean and the clients' median
    gap cannot say whether a slow run had every step slower or a few long
    ones."""
    hist = monitor.histogram("serving/step_time").labels(phase=phase)
    bounds, counts = hist._bucket_rows()[:2]
    return dict(zip(bounds + (float("inf"),), counts))


def free_pools(engine):
    """Every pool the engine holds on the device, K/V and state: the
    reference at the longest sequence does not fit beside them."""
    for cache in list(engine.caches.values()) + list(engine.states.values()):
        for name in cache.pool_names:
            setattr(cache, name, None)


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
        steps_before = step_durations(monitor)
        snap_before = monitor.snapshot()
        t_begin = time.perf_counter()
        setup_s = t_begin - spec["t0"]
        time.sleep(spec["seconds"])
        t_end = time.perf_counter()
        snap_after = monitor.snapshot()
        steps_after = step_durations(monitor)
        compiles_in_window = spec["compiles"].compiles - compiles_before

        traced = {}
        if spec["trace"]:
            with profiler_slice(traced):
                sleep_through_a_prefill(monitor, float(tr["trace_s"]))
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
                                  for g, k in engine.caches.items()},
               "state_slots": {g: s.num_slots
                               for g, s in engine.states.items()}}
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
    dropped = (_pairs_dropped(counters, cfg)
               if "num_experts_per_tok" in spec["config"] else None)
    free_pools(engine)
    del engine
    t_ref = time.perf_counter()
    worst, share = _check_against_reference(model, spec["config"], records,
                                            tr, spec["seed"])
    lap.split["reference_after_window"] = time.perf_counter() - t_ref
    checks = {
        "requests_ok": bool(ended) and not failed,
        "no_compile_in_window": compiles_in_window == 0,
        **family_checks(spec["config"], paths, dropped),
        "reference_margin": worst is not None and worst <= LOGIT_MARGIN,
        "reference_share_near": (share is not None
                                 and share >= MIN_SHARE_WITHIN),
    }
    phases = {k.split("phase=")[1].split("}")[0]: round(1e3 * v / max(
        host_phases.program_steps(counters), 1), 3)
        for k, v in counters.items()
        if k.startswith("serving/host_time{") and k.endswith(":sum")}
    log(f"host phases, ms a program step: {phases}")
    log("decode steps in the window by duration, {seconds up to: steps}: "
        f"{ {b: n - steps_before[b] for b, n in steps_after.items() if n - steps_before[b]} }")
    log(f"compiles in window {compiles_in_window}; attention paths {paths}; "
        f"routed pairs not counted {dropped}")
    log(f"set-up split (s): {lap}")
    return {"checks": checks, "attempted": len(ended),
            "failed": len(failed), "end_to_end": end_to_end,
            "timings": timings, "counters": counters, "traced": traced}
