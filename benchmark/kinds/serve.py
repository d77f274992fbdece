"""kind `serve`: `LLMEngine` behind `serving.api.start_api_server`, loaded
over a real localhost socket by a closed loop of clients - each sends
`POST /v1/completions` with `stream: true`, reads the SSE stream to its
end, and sends its next request at once (no think time).  Clients are
threads of this process: the chip belongs to one process.

Traffic parameters (benchmark/traffic/<name>.json): clients, prompt_len
and max_tokens as [[value, count], ..] (see lib/traffic.py), engine (the
keyword arguments of `EngineConfig`), warmup_s, trace_s, check_requests.

Set-up warms every program the window uses: one request of each prompt
length alone, then the closed loop for `warmup_s`.  The loop keeps running
into the window, so the window sees a steady state at both ends.  When it
closes, requests in flight are abandoned and counted neither way.

attempted = requests sent inside the window that ended inside it;
failed = of those, any with a non-200 answer, a stream error, a token
count other than max_tokens, or a finish_reason other than "stop".
Latencies are taken over every request sent inside the window that did not
fail, from the tokens that reached its client before the window closed.

correct: no failed request; nothing compiled inside the window; prefill
took the flash kernel and decode the ragged kernel, with no fallback other
than `ragged_fallback:chunk_gt_1`; and for `check_requests` finished
requests, chosen from the seed, every served token's logit in the plain
reference's full forward over prompt + served tokens lies within
LOGIT_MARGIN of that position's largest logit.
"""
import http.client
import json
import random
import threading
import time

from benchmark.lib import reference_gpt, stats
from benchmark.lib.common import (Laps, build_model, fold_seed, log,
                                  profiler_slice)
from benchmark.lib.traffic import Requests

# Greedy decoding through the bf16 engine picks a token whose float32
# reference logit may sit just under the reference's own maximum (random
# weights put near-ties everywhere).  The chip showed at most 0.03 logits
# (PERF.md, PR 24); logits here have a standard deviation near 0.9, and a
# token decoded from a wrong KV block lands about four deviations (3.5)
# under the maximum, so 0.25 passes rounding and fails a wrong cache.
LOGIT_MARGIN = 0.25
ALLOWED_FALLBACKS = ("ragged_fallback:chunk_gt_1",)
SOCKET_TIMEOUT_S = 900.0      # a cold first request compiles its programs


def send_one(host, port, index, prompt, max_tokens, stop, deadline_s=None):
    """One streamed completion -> its record: send time, arrival time of
    every token, outcome.  `ended` stays None if `stop` was set before the
    stream's end (the request is abandoned)."""
    body = {"prompt": prompt, "max_tokens": max_tokens, "stream": True}
    if deadline_s is not None:      # else the server's default budget
        body["deadline_s"] = deadline_s
    body = json.dumps(body)
    rec = {"index": index, "prompt": prompt, "want": max_tokens,
           "tokens": [], "arrivals": [], "error": None, "reason": None,
           "ended": None, "client_s": None}
    conn = http.client.HTTPConnection(host, port, timeout=SOCKET_TIMEOUT_S)
    rec["sent"] = time.perf_counter()
    try:
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            rec["error"] = f"HTTP {resp.status}"
        for raw in resp if resp.status == 200 else ():
            if stop.is_set():
                return rec
            line = raw.strip()
            if not line.startswith(b"data: ") or line == b"data: [DONE]":
                continue
            now = time.perf_counter()
            choice = json.loads(line[len(b"data: "):])["choices"][0]
            toks = choice.get("token_ids") or []
            rec["tokens"].extend(toks)
            rec["arrivals"].extend([now] * len(toks))
            rec["reason"] = choice.get("finish_reason") or rec["reason"]
    except (OSError, http.client.HTTPException, ValueError) as e:
        rec["error"] = repr(e)
    finally:
        conn.close()
    rec["ended"] = time.perf_counter()
    return rec


class _Client(threading.Thread):
    """One closed-loop caller: the next request goes out as soon as the
    last reply has ended.  `client_s` of a record is the time the client
    itself spent between the two (making the next request)."""

    def __init__(self, shared, host, port):
        super().__init__(daemon=True)
        self.shared, self.host, self.port = shared, host, port
        self.records = []

    def run(self):
        sh = self.shared
        last_end = None
        while not sh["stop"].is_set():
            with sh["lock"]:
                index = sh["next"]
                sh["next"] += 1
            prompt, max_tokens = sh["requests"].request(index)
            made = time.perf_counter()
            rec = send_one(self.host, self.port, index, prompt, max_tokens,
                           sh["stop"])
            if last_end is not None:
                rec["client_s"] = made - last_end
            last_end = rec["ended"]
            self.records.append(rec)


def _failed(rec):
    return (rec["error"] is not None or rec["reason"] != "stop"
            or len(rec["tokens"]) != rec["want"])


def _monitor_delta(before, after):
    """after - before over `monitor.snapshot()` dicts, for the counters
    and histograms (count and sum) the layer metrics read."""
    def flat(snap):
        out = {}
        for name, val in snap.items():
            series = val if isinstance(val, dict) and not (
                "count" in val and "sum" in val) else {"": val}
            for label, v in series.items():
                key = f"{name}{{{label}}}" if label else name
                if isinstance(v, dict):
                    out[key + ":count"] = v.get("count", 0)
                    out[key + ":sum"] = v.get("sum", 0.0)
                elif isinstance(v, (int, float)):
                    out[key] = v
        return out

    a, b = flat(before), flat(after)
    return {k: b[k] - a.get(k, 0) for k in b}


def _check_against_reference(model, cfg, records, traffic, seed):
    """Worst margin over `check_requests` finished requests (see the
    module docstring); every sequence is padded to one fixed length so the
    reference compiles once for a traffic mix (causal: padding at the end
    changes no earlier position)."""
    import numpy as np

    done = [r for r in records if not _failed(r)]
    picked = random.Random(fold_seed(seed)).sample(
        done, min(int(traffic["check_requests"]), len(done)))
    if not picked:
        return None
    longest = (max(v for v, _ in traffic["prompt_len"])
               + max(v for v, _ in traffic["max_tokens"]))
    width = -(-longest // 128) * 128
    ids = np.zeros((len(picked), width), np.int32)
    for row, r in zip(ids, picked):
        seq = r["prompt"] + r["tokens"]
        row[:len(seq)] = seq
    margins, spread = reference_gpt.greedy_margins(
        reference_gpt.params_from_model(model), ids,
        heads=cfg.num_attention_heads, eps=float(cfg.layer_norm_epsilon))
    margins, spread = np.asarray(margins), np.asarray(spread)
    worst, stds = 0.0, []
    for i, r in enumerate(picked):
        lo = len(r["prompt"]) - 1           # logits that chose token 0
        hi = lo + len(r["tokens"])
        if not np.isfinite(margins[i, lo:hi]).all():
            return float("inf")
        worst = max(worst, float(margins[i, lo:hi].max()))
        stds.append(float(spread[i, lo:hi].mean()))
    log(f"reference check on {len(picked)} requests (indices "
        f"{[r['index'] for r in picked]}): worst margin {worst:.4f} "
        f"logits (limit {LOGIT_MARGIN}), logit std {sum(stds)/len(stds):.3f}")
    return worst


def run(spec):
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.ops.pallas_ops import attention_path_counts
    from paddle_tpu.serving import EngineConfig, LLMEngine
    from paddle_tpu.serving.api import start_api_server

    tr = spec["traffic"]
    lap = Laps(spec["t0"])
    model, cfg = build_model(spec["config"], spec["seed"])
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
                raise RuntimeError(f"warm-up request failed: {_brief(rec)}")
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
            c.join(timeout=30)
        server.stop()
    if any(c.is_alive() for c in clients):
        raise RuntimeError("a client thread did not stop")

    window_s = t_end - t_begin
    records = [r for c in clients for r in c.records]
    # tokens that reached a client inside the window, whoever sent them
    tokens_in = sum(t_begin <= t < t_end
                    for r in records for t in r["arrivals"])
    sent = [r for r in records if t_begin <= r["sent"] < t_end]
    ended = [r for r in sent
             if r["ended"] is not None and r["ended"] < t_end]
    failed = [r for r in ended if _failed(r)]
    # latencies: every request sent in the window that did not fail, over
    # the tokens that reached its client before the window closed (so a
    # request still streaming at the close counts with what it had)
    live = [r for r in sent if not any(r is f for f in failed)]
    seen = [[t for t in r["arrivals"] if t < t_end] for r in live]
    end_to_end = {"serve_tokens_per_s": tokens_in / window_s,
                  "setup_s": setup_s}
    timings = {"window_s": window_s, "requests": len(ended),
               "setup_split_s": lap.split,
               "compiles_in_window": compiles_in_window,
               "max_num_seqs": int(tr["engine"]["max_num_seqs"])}
    ttft = [(a[0] - r["sent"]) * 1e3 for a, r in zip(seen, live) if a]
    gaps = [g * 1e3 for g in stats.pooled_gaps(seen)]
    if ttft and gaps:
        client = [r["client_s"] * 1e3 for r in live
                  if r["client_s"] is not None]
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
            f"{end_to_end['itl_p95_ms']:.2f} ms over {n_gaps}; client's "
            f"own time between reply and next send p95 "
            f"{stats.percentile(client, 95)[0] if client else 0:.2f} ms")
    for r in failed[:3]:
        log(f"failed request: {_brief(r)}")

    paths = attention_path_counts()
    fallbacks = {k: v for k, v in paths.items()
                 if "_fallback:" in k and k not in ALLOWED_FALLBACKS}
    t_ref = time.perf_counter()
    worst = _check_against_reference(model, cfg, records, tr, spec["seed"])
    lap.split["reference_after_window"] = time.perf_counter() - t_ref
    checks = {
        "requests_ok": bool(ended) and not failed,
        "no_compile_in_window": compiles_in_window == 0,
        "kernel_paths": paths.get("attn_kernel", 0) >= 1
        and paths.get("ragged_kernel", 0) >= 1 and not fallbacks,
        "reference_margin": worst is not None and worst <= LOGIT_MARGIN,
    }
    log(f"compiles in window {compiles_in_window}; attention paths {paths}")
    log(f"set-up split (s): {lap}")
    return {"checks": checks, "attempted": len(ended),
            "failed": len(failed), "end_to_end": end_to_end,
            "timings": timings,
            "counters": _monitor_delta(snap_before, snap_after),
            "traced": traced}


def _brief(rec):
    return {k: rec[k] for k in ("index", "want", "error", "reason")} | {
        "got": len(rec["tokens"]), "prompt_len": len(rec["prompt"])}
