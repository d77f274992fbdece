"""Serving smoke: boot `LLMEngine` on a tiny GPT, run a mixed-length batch,
assert throughput > 0 tokens/s, and print the serving/* monitor metrics.

Runnable anywhere (CPU included):

    JAX_PLATFORMS=cpu PTPU_MONITOR=1 python scripts/serve_smoke.py

Low-bit mode (the paddle_tpu.lowbit runtime end-to-end):

    python scripts/serve_smoke.py --quantize int8 --kv-cache-dtype int8

--quantize swaps every Linear for a packed `WeightOnlyLinear`;
--kv-cache-dtype int8 serves from a quantized KV pool (asserting it
holds ≥1.9× the blocks of the fp pool for the same byte budget).

Trace mode (the monitor v2 observability layer end-to-end):

    python scripts/serve_smoke.py --trace

--trace enables span tracing, boots the /metrics //healthz //traces
endpoint on an ephemeral port, and asserts the ISSUE-5 acceptance: the
run must yield serving/ttft + serving/tpot histograms with nonzero
counts and p50/p95, a Chrome/Perfetto-loadable trace JSON in which one
request's queue/prefill/decode spans are parent-linked under a single
trace_id, and live endpoint responses; it prints the TTFT/TPOT
percentiles plus a sample request trace.

Perf mode (the serving loop's host phases and goodput end-to-end):

    python scripts/serve_smoke.py --perf

--perf enables PTPU_PERF accounting and asserts the ISSUE-6 acceptance
surface: the serving step's host phases (serving/host_time, always on)
are populated and `perf.report()` prints them, a 3→5 batch crossing
makes zero fresh compiles and zero new
`jit/recompile_cause{fn=serving:*}` entries (the ragged acceptance
invariant), and `serving/padding_waste` +
`serving/goodput_tokens_per_s` are live.

API mode (the ISSUE-19 OpenAI-compatible front door end-to-end):

    python scripts/serve_smoke.py --api

--api boots `serving.api.ApiServer` over the same engine and asserts
the ISSUE-19 acceptance: a streamed /v1/completions over a real
socket is token-identical to `engine.generate()` (greedy AND
fixed-seed sampled), per-tenant `serving_tenant_*{tenant=...}` series
ride the live /metrics endpoint, and under an injected SLO burn a
best-effort request is refused with HTTP 429 + error code "shed"
while an interactive one still completes.

Memobs mode (the ISSUE-20 memory microscope end-to-end):

    python scripts/serve_smoke.py --memobs

--memobs enables PTPU_MEMOBS-style block-lifecycle accounting and
asserts the ISSUE-20 acceptance: the /kv pool map and /memory/timeline
ring answer on the live endpoint, a tiny-pool twin engine driven into
an eviction storm produces EXACTLY ONE rate-limited kv_pressure flight
dump whose ranked holders name the actual top block-holding
request/tenant, an admission failure inside the cooldown is suppressed
(never a second dump), and compiles stay FLAT under both pressure
events.

tests/test_serving.py runs the plain mode, tests/test_lowbit.py the
quantized one, tests/test_trace.py + test_perf.py lean on the combined
--trace --perf invocation (all fast tier), so each is a "does the
engine boot outside the test harness" guard.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
os.environ.setdefault("PTPU_FORCE_PLATFORM", "cpu")   # drop on a TPU host
os.environ.setdefault("PTPU_MONITOR", "1")

import jax

if os.environ.get("PTPU_FORCE_PLATFORM") == "cpu":
    jax.config.update("jax_platforms", "cpu")

import numpy as np
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTForCausalLM, gpt_test_config
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams


def main():
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quantize", choices=["int8", "int4"], default=None,
                    help="weight-only quantize the model (lowbit)")
    ap.add_argument("--kv-cache-dtype", choices=["int8"], default=None,
                    help="serve from a quantized KV pool (lowbit)")
    ap.add_argument("--trace", action="store_true",
                    help="enable span tracing + the live endpoint and "
                         "assert/print the v2 observability surface")
    ap.add_argument("--perf", action="store_true",
                    help="enable perf attribution and assert/print the "
                         "host-phase table, goodput gauges and compiles "
                         "FLAT across a batch crossing")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="assert the ISSUE-15 automatic-prefix-caching "
                         "surface (hits, hit_tokens=(N-1)*prefix_len, "
                         "flat compiles across hit/miss)")
    ap.add_argument("--spec", action="store_true",
                    help="assert the ISSUE-15 speculative-decoding "
                         "surface (accept_rate>0, >1 token per decode "
                         "step on a repetitive workload, flat compiles)")
    ap.add_argument("--slo", action="store_true",
                    help="assert the ISSUE-16 request-plane surface "
                         "(deadline reqlog event, kept tail-sampled "
                         "trace, ttft exemplar, live + fleet-merged "
                         "slo/burn_rate)")
    ap.add_argument("--api", action="store_true",
                    help="assert the ISSUE-19 API surface (streamed "
                         "/v1/completions token-identical to generate(), "
                         "tenant-labeled metrics, 429 shed under burn)")
    ap.add_argument("--memobs", action="store_true",
                    help="assert the ISSUE-20 memory-microscope surface "
                         "(lifecycle ledger, /kv + /memory/timeline, one "
                         "rate-limited kv_pressure dump naming the top "
                         "holder, compiles FLAT under pressure)")
    args = ap.parse_args()

    monitor.refresh()
    if args.trace:
        monitor.trace.enable(True)
    if args.perf:
        monitor.perf.enable(True)
    if args.slo:
        # the full request plane, flipped on the way PTPU_TRACE /
        # PTPU_REQLOG / PTPU_EXEMPLARS / PTPU_TRACE_TAIL / PTPU_SLO
        # would: tracing + ring-only reqlog + exemplar stamping + keep-
        # only-interesting tail sampling + two objectives (the tiny ttft
        # threshold makes every real request a budget burner, so the
        # burn gauges must go live)
        from paddle_tpu.monitor import slo as mslo

        monitor.trace.enable(True)
        monitor.enable_exemplars(True)
        monitor.reqlog.enable(True)
        monitor.trace.set_tail_budget(0)
        mslo.install(mslo.SloEngine("ttft_p95<0.0001;error_rate<0.05",
                                    min_interval=0.0))
    if args.memobs:
        # the memory microscope, flipped on the way PTPU_MEMOBS would,
        # with a throwaway flight dir for the kv_pressure forensics
        import tempfile

        os.environ["PTPU_FLIGHT_DIR"] = tempfile.mkdtemp(
            prefix="ptpu_memobs_flight_")
        monitor.memory.enable(True)
    paddle.seed(0)
    cfg = gpt_test_config(stacked_blocks=True, sequence_parallel=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    if args.quantize:
        # weight-only lives at the LAYER level, so it demos on the
        # per-layer twin of the same GPT (the stacked-blocks serving form
        # threads raw weight arrays, no Linear modules to swap): greedy
        # decode of the packed-int model must track fp within tolerance
        from paddle_tpu.core.tensor import Tensor
        from paddle_tpu.lowbit import (WeightOnlyLinear,
                                       quantize_for_inference)

        paddle.seed(0)
        dense = GPTForCausalLM(gpt_test_config(stacked_blocks=False,
                                               sequence_parallel=False))
        dense.eval()
        drng = np.random.RandomState(0)
        ids = Tensor(jnp.asarray(
            drng.randint(0, cfg.vocab_size, (2, 6)).astype(np.int32)))
        ref = np.asarray(dense.generate(ids, max_new_tokens=6)._data)
        qdense = quantize_for_inference(dense, weight_dtype=args.quantize)
        n_wol = sum(1 for l in qdense.sublayers()
                    if isinstance(l, WeightOnlyLinear))
        assert n_wol > 0, "no Linear was weight-only quantized"
        out = np.asarray(qdense.generate(ids, max_new_tokens=6)._data)
        agree = float((ref[:, 6:] == out[:, 6:]).mean())
        floor = 0.9 if args.quantize == "int8" else 0.25
        assert agree >= floor, (agree, floor)
        print(f"weight-only {args.quantize}: {n_wol} linears packed, "
              f"greedy agreement {agree:.2f} vs fp")
        del dense, qdense
    # max_num_seqs=8: headroom for the --perf leg's 3→5 batch crossing
    # (the compiles FLAT assertion needs 5 live rows)
    engine = LLMEngine(model, EngineConfig(
        block_size=16, max_num_seqs=8, kv_cache_dtype=args.kv_cache_dtype,
        metrics_port=0 if (args.trace or args.slo or args.api
                           or args.memobs) else None))
    if args.kv_cache_dtype:
        fp = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=8))
        ratio = engine.cache.num_blocks / fp.cache.num_blocks
        assert engine.cache.pool_bytes <= fp.cache.pool_bytes, (
            engine.cache.pool_bytes, fp.cache.pool_bytes)
        assert ratio >= 1.9, f"quantized pool only {ratio:.2f}x blocks"
        print(f"kv int8: {engine.cache.num_blocks} blocks vs "
              f"{fp.cache.num_blocks} fp ({ratio:.2f}x) in "
              f"{engine.cache.pool_bytes} bytes")
        del fp

    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in (4, 6, 4)]
    params = SamplingParams(max_new_tokens=6)

    t0 = time.perf_counter()
    outs = engine.generate(prompts, params)
    dt = time.perf_counter() - t0
    new_tokens = sum(len(o) - len(p) for o, p in zip(outs, prompts))
    tps = new_tokens / max(dt, 1e-9)

    assert new_tokens == 6 * len(prompts), (new_tokens, outs)
    assert tps > 0.0, tps
    assert engine.cache.blocks_in_use == 0, "finished requests must free"

    snap = monitor.snapshot()
    served = sorted(k for k in snap if k.startswith("serving/"))
    assert "serving/decode_tokens" in served, served
    print(f"generated {new_tokens} tokens in {dt:.2f}s "
          f"({tps:.1f} tokens/s, includes compiles)")
    print("serving metrics:", ", ".join(served))
    if args.quantize or args.kv_cache_dtype:
        low = sorted(k for k in snap if k.startswith("lowbit/"))
        assert low, "lowbit mode must emit lowbit/* metrics"
        print("lowbit metrics:", ", ".join(low))
    if args.perf:
        check_perf(engine, snap, cfg)
    if args.slo:   # before check_trace: that leg stops the endpoint
        check_slo(engine, cfg)
    if args.api:   # ditto — needs the live /metrics endpoint
        check_api(engine, cfg)
    if args.memobs:   # ditto — needs /kv + /memory/timeline live
        check_memobs(engine, model, cfg)
    if args.trace:
        check_trace(engine, snap, len(prompts))
    elif args.slo or args.api or args.memobs:
        monitor.stop_server()
    if args.prefix_cache or args.spec:
        check_prefix_spec(model, cfg, prefix=args.prefix_cache,
                          spec=args.spec)
    print("OK")


def check_perf(engine, snap, cfg):
    """The serving loop's host phases are populated and reported, the
    padding/goodput gauges are live, and compiles stay FLAT across a
    batch crossing."""
    from paddle_tpu.monitor import perf

    # in-situ host phases: every step observed each phase of the serving
    # loop into serving/host_time (always on; nothing is synced for it)
    phases = snap.get("serving/host_time", {})
    for name in ("engine/schedule", "engine/prepare",
                 "engine/sample_dispatch", "engine/readback",
                 "engine/emit", "engine/retire"):
        h = phases.get(f"phase={name}")
        assert h and h["count"] > 0, (f"host phase {name} not populated",
                                      sorted(phases))

    table = perf.report()
    assert "serving host phases" in table and "engine/prepare" in table, \
        table
    print(table)

    # goodput accounting populated by the main run...
    snap = monitor.snapshot()
    pad = snap.get("serving/padding_waste")
    assert pad and "kind=rows" in pad and "kind=tokens" in pad, pad
    good = snap.get("serving/goodput_tokens_per_s")
    assert good and good > 0, good

    # ...and FLAT across a 3→5 batch crossing: zero fresh compiles, zero
    # new serving recompile causes (the ragged fixed-shape invariant;
    # prompt lengths reuse already-compiled prefill programs so the
    # cause count isolates the decode path)
    def serving_causes(s):
        v = s.get("jit/recompile_cause") or {}
        return sum(n for k, n in sorted(v.items()) if "serving:" in k)

    compiles_before = sum(snap["serving/compiles"].values())
    causes_before = serving_causes(snap)
    rng = np.random.RandomState(1)
    prompts5 = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
                for n in (4, 6, 4, 6, 4)]
    engine.generate(prompts5, SamplingParams(max_new_tokens=4))
    snap = monitor.snapshot()
    d_compiles = sum(snap["serving/compiles"].values()) - compiles_before
    d_causes = serving_causes(snap) - causes_before
    assert d_compiles == 0, f"{d_compiles} fresh compiles at the crossing"
    assert d_causes == 0, f"{d_causes} new serving recompile causes"
    print(f"compiles FLAT across 3→5 crossing "
          f"(0 compiles, 0 causes); padding rows="
          f"{snap['serving/padding_waste']['kind=rows']:.3f}, goodput="
          f"{snap['serving/goodput_tokens_per_s']:.1f} tok/s")


def check_prefix_spec(model, cfg, prefix, spec):
    """ISSUE 15 acceptance, measured on this host: N requests sharing a
    prefix pay its prefill once (`serving/prefix_hit_tokens` ==
    (N-1)*prefix_len), speculative decode emits >1 accepted token per
    decode step on a repetitive workload (accept_rate > 0), and
    `serving/compiles` + `jit/recompiles{fn=serving:*}` stay FLAT across
    a second hit/miss round (all shapes fixed)."""
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    k = 3 if spec else 0
    eng = LLMEngine(model, EngineConfig(
        block_size=16, max_num_seqs=4, enable_prefix_caching=prefix,
        speculative_tokens=k))
    rng = np.random.RandomState(5)
    compiles = monitor.counter("serving/compiles")
    recompiles = monitor.counter("jit/recompiles")

    def count(c):
        snap_ = c.snapshot()
        if not isinstance(snap_, dict):
            return float(snap_ or 0)
        return sum(v for key, v in sorted(snap_.items())
                   if "serving" in key or "kind=" in key)

    if prefix:
        # N=4 requests sharing a 32-token (2-block) prefix: request 0
        # pays the prefill and populates the index; 1..3 adopt it
        shared = rng.randint(0, cfg.vocab_size, (32,)).astype(np.int32)
        tails = [rng.randint(0, cfg.vocab_size, (t,)).astype(np.int32)
                 for t in (8, 8, 12)]
        cold = np.concatenate([shared,
                               rng.randint(0, cfg.vocab_size, (8,))
                               .astype(np.int32)])
        sp = SamplingParams(max_new_tokens=4)
        eng.generate([cold], sp)
        assert eng.cache.prefix_hits == 0, eng.cache.prefix_hits
        eng.generate([np.concatenate([shared, t]) for t in tails], sp)
        hit_toks = eng.cache.prefix_hit_tokens
        assert eng.cache.prefix_hits == 3, eng.cache.prefix_hits
        assert hit_toks == 3 * 32, hit_toks     # (N-1) * prefix_len
        assert eng.cache.num_parked_blocks > 0
        snap_ = monitor.snapshot()
        assert snap_.get("serving/prefix_hits") == 3, snap_.get(
            "serving/prefix_hits")
        assert snap_.get("serving/prefix_hit_tokens") == hit_toks
        print(f"prefix cache: hits=3 hit_tokens={hit_toks} "
              f"(= (N-1)*prefix_len), parked="
              f"{eng.cache.num_parked_blocks} blocks")
        # flat compiles across a second hit/miss round: one more hit
        # (cached prefix, fresh 8-token tail) and one full miss (fresh
        # prefix, same prompt length) — every shape already compiled
        c0, r0 = count(compiles), count(recompiles)
        miss = rng.randint(0, cfg.vocab_size, (40,)).astype(np.int32)
        eng.generate([np.concatenate([shared,
                                      rng.randint(0, cfg.vocab_size, (8,))
                                      .astype(np.int32)]), miss], sp)
        dc, dr = count(compiles) - c0, count(recompiles) - r0
        assert dc == 0 and dr == 0, (dc, dr)
        assert eng.cache.prefix_hits == 4
        print("compiles FLAT across hit/miss round (0 new compiles, "
              "0 new serving recompiles)")

    if spec:
        # repetitive workload: the n-gram proposer reads the repeating
        # pattern (and the cycle greedy decoding settles into) and the
        # verify step accepts multi-token runs
        pat = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
        prompt = np.concatenate([pat] * 4)
        sp = SamplingParams(max_new_tokens=24)
        rid = eng.add_request(prompt, sp)
        try:
            decode_steps = toks_before = 0
            while eng.has_unfinished():
                was = len(eng._requests[rid].output_ids)
                eng.step()
                if len(eng._requests[rid].output_ids) > was:
                    if was > 0:
                        decode_steps += 1
                        toks_before += (len(eng._requests[rid].output_ids)
                                        - was)
            out_len = len(eng._requests[rid].output_ids)
        finally:
            eng.release_request(rid)
        assert out_len == 24, out_len
        tps_step = toks_before / max(decode_steps, 1)
        snap_ = monitor.snapshot()
        proposed = snap_.get("serving/spec_proposed", 0)
        accepted = snap_.get("serving/spec_accepted", 0)
        rate = snap_.get("serving/spec_accept_rate", 0.0)
        assert proposed > 0 and accepted > 0, (proposed, accepted)
        assert rate > 0, rate
        assert tps_step > 1.0, (
            f"spec decode emitted only {tps_step:.2f} tokens/step")
        print(f"spec decode: {tps_step:.2f} accepted tokens/decode-step, "
              f"accept_rate={rate:.2f} ({accepted}/{proposed} drafts)")
        # flat compiles on a further spec round (same shapes)
        c0, r0 = count(compiles), count(recompiles)
        eng.generate([prompt], SamplingParams(max_new_tokens=8))
        dc, dr = count(compiles) - c0, count(recompiles) - r0
        assert dc == 0 and dr == 0, (dc, dr)
        print("compiles FLAT across spec round (0 new)")


def check_slo(engine, cfg):
    """ISSUE 16 acceptance: one request's journey is traceable end to
    end — a deadline-expired request yields a reqlog event with
    finish_reason="deadline", a kept tail-sampled trace reachable from a
    serving/ttft exemplar on /metrics, and a nonzero slo/burn_rate on
    both the replica and the fleet-merged view."""
    import json
    import re
    import urllib.request
    from paddle_tpu.monitor import fleet, reqlog

    # a deadline-expired request under load: run it to its first token
    # (so it owns a TTFT observation + exemplar), let the deadline
    # lapse, and step once — the expiry sweep releases it
    rng = np.random.RandomState(7)
    prompt = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    rid = engine.add_request(prompt, SamplingParams(
        max_new_tokens=32, deadline_s=0.25))
    while engine._requests[rid].first_token_t is None:
        engine.step()
    time.sleep(0.3)
    engine.step()
    assert rid not in engine._requests, "deadline request not expired"

    # (a) the wide event, from the ring
    evs = [e for e in reqlog.recent() if e["rid"] == rid]
    assert evs, "no reqlog event for the deadline request"
    ev = evs[0]
    assert ev["finish_reason"] == "deadline", ev
    assert ev["schema_version"] == reqlog.REQLOG_SCHEMA_VERSION, ev
    assert ev["ttft_s"] and ev["ttft_s"] > 0, ev
    assert ev["generated_tokens"] > 0 and ev["prompt_tokens"] == 6, ev
    tid = ev["trace_id"]
    assert tid, ev

    # (b) its trace survived tail sampling (budget 0 = only interesting
    # kept; a deadline finish is always interesting)
    spans = monitor.trace.get_trace(tid)
    assert spans, "deadline trace was not kept by tail sampling"
    root = [s for s in spans if s["parent_id"] is None][0]
    assert root["attrs"].get("finish") == "deadline", root
    print(f"reqlog: rid={rid} finish=deadline ttft={ev['ttft_s']*1e3:.1f}ms "
          f"trace {tid} kept ({len(spans)} spans)")

    # (c) the live endpoint: a serving/ttft exemplar pointing at a kept
    # trace, a populated /requests/recent, and a live burn-rate gauge
    srv = engine.metrics_server
    txt = urllib.request.urlopen(srv.url + "/metrics",
                                 timeout=10).read().decode()
    exm = re.findall(
        r'serving_ttft_bucket\{[^}]*\} \d+ # \{trace_id="([^"]+)"\}', txt)
    assert exm, "no exemplar on serving_ttft buckets"
    ex_spans = json.loads(urllib.request.urlopen(
        srv.url + "/traces/" + exm[-1], timeout=10).read())
    assert ex_spans, "ttft exemplar points at an unknown trace"
    burns = {}
    for line in txt.splitlines():
        if line.startswith("slo_burn_rate{"):
            burns[line.rsplit(" ", 1)[0]] = float(line.rsplit(" ", 1)[1])
    assert burns and max(burns.values()) > 0, burns
    doc = json.loads(urllib.request.urlopen(
        srv.url + "/requests/recent?n=50", timeout=10).read())
    assert doc["enabled"] and doc["events"], doc
    assert any(e["rid"] == rid and e["finish_reason"] == "deadline"
               for e in doc["events"]), doc["events"]
    rep = json.loads(urllib.request.urlopen(srv.url + "/slo",
                                            timeout=10).read())
    assert rep["enabled"] and rep["objectives"], rep
    worst = max(o["burn_rate"]["fast"] for o in rep["objectives"])
    assert worst > 0, rep
    print(f"endpoint: ttft exemplar -> kept trace, /requests/recent "
          f"n={len(doc['events'])}, /slo worst fast burn {worst:.1f}x")

    # (d) the fleet-merged view: one poll of this replica must carry the
    # burn gauges through parse/merge and roll them into the router feed
    agg = fleet.FleetAggregator(endpoints=[srv.url])
    agg.poll_once()
    fleet_txt = agg.registry.export_prometheus()
    fburn = [ln for ln in fleet_txt.splitlines()
             if ln.startswith("slo_burn_rate{")
             and float(ln.rsplit(" ", 1)[1]) > 0]
    assert fburn, "no nonzero slo_burn_rate on the fleet-merged view"
    feed = agg.snapshot()
    rec = next(iter(feed.values()))
    assert rec["slo_max_burn_rate"] and rec["slo_max_burn_rate"] > 0, rec
    assert rec["slo_min_budget_remaining"] is not None, rec
    assert "serving_ttft_bucket" in fleet_txt and "# {trace_id=" in \
        fleet_txt, "exemplars must survive fleet federation"
    print(f"fleet: slo_max_burn_rate={rec['slo_max_burn_rate']:.1f} "
          f"budget_remaining={rec['slo_min_budget_remaining']:.2f} "
          f"(feed), exemplars federated")


def check_api(engine, cfg):
    """ISSUE 19 acceptance: a streamed /v1/completions over a real socket
    is token-identical to `engine.generate()` (greedy AND fixed-seed
    sampled), per-tenant serving_tenant_* series ride the live /metrics
    endpoint, and under an injected SLO burn a best-effort request is
    refused with HTTP 429 + error code "shed" while an interactive one
    on the same socket still completes."""
    import json
    import urllib.error
    import urllib.request
    from paddle_tpu.monitor import slo as mslo
    from paddle_tpu.serving import ApiServer

    # references from the same engine, BEFORE the server owns it (the
    # pump thread is the engine's only driver once it starts): prompt
    # lengths reuse the main run's compiled prefill shapes
    rng = np.random.RandomState(11)
    p_greedy = rng.randint(0, cfg.vocab_size, (4,)).astype(np.int32)
    p_seeded = rng.randint(0, cfg.vocab_size, (6,)).astype(np.int32)
    ref_greedy = engine.generate([p_greedy],
                                 SamplingParams(max_new_tokens=6))[0]
    ref_seeded = engine.generate([p_seeded], SamplingParams(
        max_new_tokens=6, do_sample=True, temperature=0.8, seed=123))[0]

    # an SLO engine every real request burns (ttft threshold below any
    # achievable first-token latency), primed pre-server for the same
    # single-driver reason: once it reports fast burn >= PTPU_SHED_BURN
    # the admission gate must shed best-effort and only best-effort
    mslo.install(mslo.SloEngine("ttft_p95<0.0001", min_interval=0.0))
    mslo.report()   # baseline sample: burn measures what comes next
    engine.generate([p_greedy], SamplingParams(max_new_tokens=2))
    from paddle_tpu.serving.scheduler import worst_fast_burn
    burn = worst_fast_burn()
    assert burn >= 2.0, f"injected burn did not register ({burn})"

    server = ApiServer(engine=engine,
                       api_keys={"sk-acme": ("acme", "interactive"),
                                 "sk-free": ("free", "best-effort")})
    try:
        def post(body, key="sk-acme"):
            req = urllib.request.Request(
                server.url + "/v1/completions",
                data=json.dumps(body).encode(),
                headers={"Authorization": "Bearer " + key,
                         "Content-Type": "application/json"})
            return urllib.request.urlopen(req, timeout=60)

        def sse_tokens(resp):
            toks, reason = [], None
            for raw in resp:
                line = raw.decode("utf-8").strip()
                if not line.startswith("data: "):
                    continue
                payload = line[len("data: "):]
                if payload == "[DONE]":
                    break
                choice = json.loads(payload)["choices"][0]
                toks.extend(choice.get("token_ids") or [])
                reason = choice.get("finish_reason") or reason
            return toks, reason

        # (a) greedy streamed completion == generate(), token for token
        toks, reason = sse_tokens(post(
            {"prompt": [int(t) for t in p_greedy], "max_tokens": 6,
             "stream": True}))
        want = [int(t) for t in ref_greedy[len(p_greedy):]]
        assert toks == want and reason == "stop", (toks, want, reason)
        # (b) fixed-seed sampled streamed completion == generate()
        toks2, reason2 = sse_tokens(post(
            {"prompt": [int(t) for t in p_seeded], "max_tokens": 6,
             "stream": True, "temperature": 0.8, "seed": 123}))
        want2 = [int(t) for t in ref_seeded[len(p_seeded):]]
        assert toks2 == want2 and reason2 == "stop", (toks2, want2, reason2)
        print(f"api: streamed /v1/completions token-identical to "
              f"generate() (greedy {toks}, seeded {toks2})")

        # (c) the tenant dimension on the live /metrics endpoint
        txt = urllib.request.urlopen(
            engine.metrics_server.url + "/metrics", timeout=10
        ).read().decode()
        for want_line in ('serving_tenant_admitted{tenant="acme"}',
                          'serving_tenant_tokens{tenant="acme"}',
                          'serving_ttft_bucket{'):
            assert want_line in txt, want_line
        assert 'tenant="acme"' in "".join(
            ln for ln in txt.splitlines()
            if ln.startswith("serving_ttft_bucket{")), (
            "no tenant-labeled ttft observation")
        print("api: serving_tenant_* series live on /metrics "
              "(tenant=acme admitted + tokens + labeled ttft)")

        # (d) shed: best-effort under burn -> 429 + code "shed";
        # interactive under the SAME burn -> 200 and completes
        try:
            post({"prompt": [int(t) for t in p_greedy], "max_tokens": 2},
                 key="sk-free")
            raise AssertionError("best-effort request was not shed")
        except urllib.error.HTTPError as e:
            assert e.code == 429, e.code
            assert e.headers.get("Retry-After"), "429 must set Retry-After"
            doc = json.loads(e.read())
            assert doc["error"]["code"] == "shed", doc
        ok = json.loads(post({"prompt": [int(t) for t in p_greedy],
                              "max_tokens": 2}).read())
        assert ok["choices"][0]["finish_reason"] == "stop", ok
        shed_txt = urllib.request.urlopen(
            engine.metrics_server.url + "/metrics", timeout=10
        ).read().decode()
        assert 'serving_tenant_shed{tenant="free"}' in shed_txt
        print("api: best-effort shed with 429 code=shed under burn "
              "(interactive still served)")
    finally:
        server.stop()


def check_memobs(engine, model, cfg):
    """ISSUE 20 acceptance: the memory microscope end to end — the main
    run populated the block-lifecycle ledger, the published /kv pool map
    and the /memory/timeline ring on the live endpoint; then a tiny-pool
    twin engine (same compiled shapes) is driven into an eviction storm
    with live holders, which must produce EXACTLY ONE rate-limited
    kv_pressure flight dump whose ranked holders name the actual top
    block-holding request/tenant; an admission failure inside the
    cooldown is a suppressed trigger, never a second dump — with zero
    fresh compiles throughout (neither pressure path reaches prefill on
    a new shape)."""
    import glob
    import json
    import urllib.request
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    # (a) the shared engine's main run already drove the microscope
    ev = engine.cache.acct.events
    assert ev["alloc"] > 0 and ev["free"] > 0, ev
    srv = engine.metrics_server
    kv = json.loads(urllib.request.urlopen(
        srv.url + "/kv", timeout=10).read())
    assert kv["enabled"] and kv["snapshot"], kv
    pool = kv["snapshot"]
    assert pool["free"] + pool["in_use"] == pool["num_blocks"], pool
    assert pool["events"]["alloc"] > 0, pool
    tl = json.loads(urllib.request.urlopen(
        srv.url + "/memory/timeline", timeout=10).read())
    assert tl["enabled"] and tl["n"] > 0, tl
    last = tl["readings"][-1]
    assert last["host_rss"] and last["host_rss"] > 0, last
    assert last["ts"] >= tl["readings"][0]["ts"], tl["readings"]
    print(f"memobs: /kv pool map live ({pool['num_blocks']} blocks, "
          f"ledger alloc={pool['events']['alloc']}), /memory/timeline "
          f"n={tl['n']} (rss={last['host_rss'] >> 20}MiB)")

    # (b) pressure forensics on a tiny-pool twin (same block_size /
    # max_num_seqs as the shared engine, so every program is already
    # compiled).  Four requests fill the 4-block pool one block each;
    # ~12 quiet decode steps build the storm detector's zero baseline;
    # then every row crosses into its second block on the SAME step —
    # the pool can only re-home two, so two rows are preempted at once:
    # an eviction storm.  Each prefill carries the rows admitted before
    # it one token further, so request i's prompt is i tokens longer
    # and all four reach position 16 together.  The dump must name the
    # oldest surviving holder (tenant acme).
    eng = LLMEngine(model, EngineConfig(
        block_size=16, num_blocks=4, max_num_seqs=8))
    rng = np.random.RandomState(21)
    prompts = [rng.randint(0, cfg.vocab_size, (4 + i,)).astype(np.int32)
               for i in range(4)]
    rids = [eng.add_request(p, SamplingParams(
        max_new_tokens=16, tenant="acme" if i == 0 else "hog"))
        for i, p in enumerate(prompts)]
    try:
        for _ in range(10):   # 4 prefills + quiet decode: the twin's
            eng.step()        # own program cache compiles HERE, pre-
            # baseline, and the storm detector banks >= 8 zero-eviction
            # observations
        snap_ = monitor.snapshot()
        compiles0 = sum(snap_["serving/compiles"].values())
        steps = 10
        while eng.has_unfinished() and steps < 300:
            eng.step()
            steps += 1
        assert not eng.has_unfinished(), f"no drain in {steps}"
    finally:
        for r in rids:
            eng.release_request(r)
    snap_ = monitor.snapshot()
    assert snap_.get("memory/eviction_storms", 0) >= 1, (
        "block-boundary crossing did not register as a storm")
    dumps = sorted(glob.glob(os.path.join(
        os.environ["PTPU_FLIGHT_DIR"], "*kv_pressure*.json")))
    assert len(dumps) == 1, f"want exactly one dump, got {dumps}"
    with open(dumps[0]) as f:
        extra = json.load(f)["extra"]
    assert extra["trigger"] == "eviction_storm", extra
    assert extra["replica"].get("host"), extra
    top = extra["holders"]["requests"][0]
    assert top["rid"] == rids[0] and top["tenant"] == "acme", (top, rids)
    assert top["blocks"] >= 2, top   # just crossed into its 2nd block
    tenants = extra["holders"]["tenants"]
    assert tenants and tenants[0]["tenant"] in ("acme", "hog"), tenants
    assert sum(t["blocks"] for t in tenants) <= 4, tenants

    # the cooldown is GLOBAL: an admission failure right after the storm
    # is a new trigger but must be suppressed, never a second dump.  A
    # 2-block twin makes a 40-token prompt (3 blocks) unholdable, so it
    # fails at schedule() — before prefill, hence before any compile
    eng2 = LLMEngine(model, EngineConfig(
        block_size=16, num_blocks=2, max_num_seqs=8))
    big = rng.randint(0, cfg.vocab_size, (40,)).astype(np.int32)
    bid = eng2.add_request(big, SamplingParams(max_new_tokens=2,
                                               tenant="hog"))
    try:
        try:
            eng2.step()
            raise AssertionError("too-big admission did not fail")
        except RuntimeError as e:
            assert "KV cache too small" in str(e), e
        dumps2 = glob.glob(os.path.join(
            os.environ["PTPU_FLIGHT_DIR"], "*kv_pressure*.json"))
        assert len(dumps2) == 1, f"rate limit leaked a dump: {dumps2}"
        snap_ = monitor.snapshot()
        assert snap_.get("memory/pressure_dumps") == 1, snap_.get(
            "memory/pressure_dumps")
        assert snap_.get("memory/pressure_suppressed", 0) >= 1, (
            "admission failure inside the cooldown was not rate-limited")
        d_compiles = sum(snap_["serving/compiles"].values()) - compiles0
        assert d_compiles == 0, f"{d_compiles} compiles under pressure"
    finally:
        eng2.release_request(bid)
    print(f"memobs: eviction storm -> one kv_pressure dump, top holder "
          f"rid={rids[0]} tenant=acme ({top['blocks']} blocks); "
          f"admission failure inside cooldown suppressed; "
          f"compiles FLAT under pressure")


def check_trace(engine, snap, n_requests):
    """ISSUE 5 acceptance (a)+(b) + endpoint: latency histograms with
    percentiles, a parent-linked per-request trace, a loadable chrome
    JSON, and live /metrics //healthz //traces responses."""
    import json
    import tempfile
    import urllib.request

    # (a) TTFT/TPOT histograms with nonzero counts and p50/p95
    for name in ("serving/ttft", "serving/tpot"):
        h = snap.get(name)
        assert h and h["count"] > 0, (name, h)
        assert "p50" in h and "p95" in h, (name, h)
    ttft, tpot = snap["serving/ttft"], snap["serving/tpot"]
    assert ttft["count"] == n_requests, ttft
    print(f"ttft: n={ttft['count']} p50={ttft['p50']*1e3:.1f}ms "
          f"p95={ttft['p95']*1e3:.1f}ms | tpot: n={tpot['count']} "
          f"p50={tpot['p50']*1e3:.2f}ms p95={tpot['p95']*1e3:.2f}ms")

    # (b) one request's spans, parent-linked under one trace_id
    spans = engine.request_trace(0)
    assert spans, "request 0 left no trace"
    roots = [s for s in spans if s["parent_id"] is None]
    assert len(roots) == 1 and roots[0]["name"] == "serving/request", spans
    root = roots[0]
    ids = {s["span_id"] for s in spans}
    assert all(s["trace_id"] == root["trace_id"] for s in spans)
    assert all(s["parent_id"] in ids for s in spans
               if s["parent_id"] is not None)
    names = [s["name"] for s in spans]
    for needed in ("serving/queue_wait", "serving/prefill",
                   "serving/decode_step"):
        assert needed in names, names
    print("request 0 trace:")
    for s in spans:
        indent = "  " if s["parent_id"] else ""
        print(f"  {indent}{s['name']:24s} {s['dur_us']/1e3:9.2f} ms "
              f"{s['attrs']}")

    path = os.path.join(tempfile.gettempdir(),
                        f"ptpu_serve_trace_{os.getpid()}.json")
    monitor.trace.export_chrome_trace(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    mine = [e for e in events
            if e.get("args", {}).get("trace_id") == root["trace_id"]]
    # the steps the request rode (serving/step and its phases) are shared
    # spans: stored once, exported once under their own ids
    steps = {s["span_id"] for s in spans if s["name"] == "serving/step"}
    own = [s for s in spans
           if s["span_id"] not in steps and s["parent_id"] not in steps]
    assert steps and len(own) < len(spans), names
    assert len(mine) == len(own), (len(mine), len(own))
    assert all({"name", "ph", "ts", "dur", "pid", "tid"} <= set(e)
               for e in mine)
    print(f"chrome trace: {path} ({len(events)} events)")

    # live endpoint
    srv = engine.metrics_server
    txt = urllib.request.urlopen(srv.url + "/metrics",
                                 timeout=10).read().decode()
    assert "serving_ttft_bucket" in txt and "serving_tpot_count" in txt
    hz = json.loads(urllib.request.urlopen(srv.url + "/healthz",
                                           timeout=10).read())
    assert hz["status"] == "ok" and hz["trace_enabled"]
    tr = json.loads(urllib.request.urlopen(
        srv.url + "/traces/" + root["trace_id"], timeout=10).read())
    assert len(tr) == len(spans)
    print(f"endpoint {srv.url}: /metrics /healthz /traces ok")
    monitor.stop_server()


if __name__ == "__main__":
    main()
