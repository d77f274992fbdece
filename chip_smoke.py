"""Does the system still start on the chip?  One command, three legs:

    python chip_smoke.py             # one TPU chip; anything else is an error
    python chip_smoke.py --chips 4   # train leg only, under init_mesh(dp=2, mp=2)
    python chip_smoke.py --tiny      # same legs at a toy width on whatever
                                     # platform is present (CPU rehearsal:
                                     # JAX_PLATFORMS=cpu PTPU_PALLAS_INTERPRET=1)

train  GPT-3 1.3B (`gpt3_1p3b_config`, all 24 layers, bf16, bf16 AdamW
       moments) through `parallel.init_mesh` / `place_model` /
       `jit.compile`, 4 steps on one fixed B2xS2048 batch.  Loss must start
       near ln(vocab) (random weights), stay finite and fall every step.
serve  the same model behind `LLMEngine(EngineConfig())` +
       `serving.api.start_api_server`: /v1/completions over a localhost
       socket — plain, streamed (token-identical to plain), and two
       concurrent — then a second engine with int8 KV at block_size=32.
       Served tokens are checked against a teacher-forced dense forward of
       the same model: every greedy token must sit in the reference top-k.
families  the second, third and fourth served family, each a small model
       at its REAL head geometry through `LLMEngine.generate`: afmoe (48
       query heads over 8 K/V heads of 128, a sliding and a full layer:
       two cache groups), lfm2_moe (32 over 8 heads of 64, gated short
       convolutions: a K/V group and a state group) and mistral4 (32 heads
       of 64 + 64 over a latent row of 256 + 64: ONE pool a layer, prefill
       expanded through flash, decode absorbed through the latent kernel,
       positions past a shrunk original context), hidden 256, 8 experts
       top-2.  Two prompts of 128 and 256 in a batch of 4 rows, 32 tokens
       each, against the family's plain float32 reference
       (`benchmark/lib/reference_afmoe.py`, `reference_lfm2.py`,
       `reference_mistral4.py`): every served token must sit in the
       reference top-k.

All legs run with PTPU_ATTN_DEBUG=1 and assert the attention gates took
the Pallas kernels (flash in the train step and prefill, ragged in decode,
fp and int8, grouped heads of 128 and of 64 lanes, latent rows) — a kernel that gives
way to its XLA reference fails the smoke.

The parent never imports JAX: a chip belongs to one process, so each leg is
its own child, one after the other, sharing the persistent compile cache.
Progress goes to stderr.  stdout ends with two JSON lines: the per-leg
report (versions, wall seconds, compile counts, attention-path counters),
then the verdict, `{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": 1}}` with the device as JAX reports it.  Exit code 0 only if every
leg passed.  With no accelerator the script exits non-zero before compiling
anything and prints nothing on stdout.
"""
import argparse
import json
import os
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
BUDGET_S = 1150.0          # the contract allows 1200 s, compilation included
NO_CHIP_RC = 3             # child exit code: platform is not tpu

TOP_K = 8                  # served greedy tokens must rank this high in
#                            the dense reference (bf16 argmax near-ties
#                            move a token a few ranks, a wrong KV block
#                            moves it thousands)


# ---------------------------------------------------------------------------
# parent: no jax in this process
# ---------------------------------------------------------------------------

def _run_leg(leg, args, deadline):
    """Run one leg in a child (own session, killed with its group) and
    return its result dict — the child's last stdout line."""
    cmd = [sys.executable, os.path.abspath(__file__), "--leg", leg,
           "--chips", str(args.chips)] + (["--tiny"] if args.tiny else [])
    env = dict(os.environ, PTPU_ATTN_DEBUG="1")
    t0 = time.monotonic()
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, env=env,
                            cwd=ROOT, start_new_session=True)
    timer = threading.Timer(max(1.0, deadline - t0), _kill_group, (proc,))
    timer.start()
    try:
        last = ""
        for line in proc.stdout:
            line = line.rstrip("\n")
            if line:
                last = line
                # progress goes to stderr: stdout carries the summary only
                print(f"[{leg}] {line}", file=sys.stderr, flush=True)
        rc = proc.wait()
    finally:
        timer.cancel()
        _kill_group(proc)
    try:
        result = json.loads(last)
    except ValueError:
        result = {}
    if not isinstance(result, dict) or result.get("leg") != leg:
        result = {"leg": leg, "ok": False,
                  "error": f"child exited rc={rc} without a result"}
    result["rc"] = rc
    result["wall_s"] = round(time.monotonic() - t0, 1)
    if rc != 0:
        result["ok"] = False
    return result


def _kill_group(proc):
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()


def parent_main(args):
    if not os.path.isdir(os.path.join(ROOT, "paddle_tpu")):
        sys.exit("chip_smoke: no paddle_tpu package beside this script — "
                 "run it from a checkout of the repo")
    t0 = time.monotonic()
    legs = ["train"] if args.chips > 1 else ["train", "serve", "families"]
    results = []
    for leg in legs:
        res = _run_leg(leg, args, t0 + BUDGET_S)
        results.append(res)
        if res["rc"] == NO_CHIP_RC:
            sys.exit(f"chip_smoke: {res.get('error', 'no TPU')}")
        if not res["ok"]:
            break          # later legs would only burn the budget
    ok = len(results) == len(legs) and all(r["ok"] for r in results)
    # every leg reports the same process-wide facts; say them once
    shared = {}
    for key in ("device", "versions", "cache_dir"):
        shared[key] = results[0].get(key)
        for r in results:
            r.pop(key, None)
    summary = {"ok": ok, **shared, "chips": args.chips,
               "wall_s": round(time.monotonic() - t0, 1),
               "legs": {r.pop("leg"): r for r in results}}
    if args.tiny:
        summary["tiny"] = True
    device = shared["device"]
    if device is None:       # the first child died before it named a device
        print(json.dumps(summary), file=sys.stderr, flush=True)
        sys.exit(1)
    # stdout: the per-leg report, then the verdict as the last line, which
    # is exactly {"ok", "device": {"platform", "kind", "count"}}
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    sys.exit(0 if ok else 1)


# ---------------------------------------------------------------------------
# children: one leg each
# ---------------------------------------------------------------------------

class _CompileStats:
    """Backend compiles, their seconds, and persistent-cache traffic, as
    jax.monitoring reports them for this process."""

    def __init__(self):
        from jax import monitoring

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def report(self):
        return {"compiles": self.compiles,
                "compile_s": round(self.compile_s, 1),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def _start_child(args):
    """Platform check FIRST (before paddle_tpu is imported or anything
    compiles), then the compile cache.  Returns (device info, stats)."""
    import jax

    try:
        devs = jax.devices()
        found = f"JAX platform is {devs[0].platform!r}, not 'tpu'"
    except RuntimeError as e:          # no backend could be initialised
        devs, found = [], f"JAX found no device: {e}"
    if not devs or (devs[0].platform != "tpu" and not args.tiny):
        print(json.dumps({"leg": args.leg, "ok": False, "error":
                          found + " (--tiny rehearses on other platforms)"}),
              flush=True)
        sys.exit(NO_CHIP_RC)
    dev = devs[0]
    if len(devs) < args.chips:
        raise SystemExit(f"--chips {args.chips} but JAX sees {len(devs)} "
                         f"{dev.platform} device(s)")
    stats = _CompileStats()
    from paddle_tpu.jit import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jaxlib

    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        import libtpu

        versions["libtpu"] = libtpu.__version__
    except ImportError:
        versions["libtpu"] = None
    info = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(devs)},
            "versions": versions, "cache_dir": cache_dir}
    return info, stats


def _gpt_config(tiny):
    from paddle_tpu.models import gpt3_1p3b_config, gpt_test_config

    if tiny:
        # gpt_test_config widened to the narrowest kernel-eligible heads
        # (D=64, H*D=128) so the rehearsal walks the same gates
        return gpt_test_config(stacked_blocks=True, sequence_parallel=False,
                               hidden_size=128, num_attention_heads=2,
                               intermediate_size=256,
                               max_position_embeddings=512)
    return gpt3_1p3b_config(stacked_blocks=True)


def _require(cond, msg):
    """The smoke's checks must survive `python -O`, so no bare assert."""
    if not cond:
        raise AssertionError(msg)


def _check_paths(counts, need, allowed_fallbacks=()):
    """Every `need` counter fired, and no gate fell back except as
    allowed."""
    for name in need:
        _require(counts.get(name, 0) >= 1,
                 f"attention path {name!r} never taken: {counts}")
    bad = {k: v for k, v in counts.items()
           if "_fallback:" in k and k not in allowed_fallbacks}
    _require(not bad, f"kernel gave way to a reference path: {bad}")


def train_leg(args, mesh=None, batch=2):
    import math

    import numpy as np
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import jit, optimizer, parallel
    from paddle_tpu.models import GPTForCausalLM, GPTPretrainingCriterion
    from paddle_tpu.ops.pallas_ops import attention_path_counts

    cfg = _gpt_config(args.tiny)
    seq, steps = (128, 4) if args.tiny else (2048, 4)
    if mesh is None:
        mesh = dict(dp=2, mp=2) if args.chips == 4 else {}
    n_dev = math.prod(mesh.values()) if mesh else 1

    paddle.seed(0)
    parallel.init_mesh(**mesh)
    model = parallel.place_model(GPTForCausalLM(cfg))
    if jax.devices()[0].platform == "tpu":
        model.bfloat16()
    crit = GPTPretrainingCriterion(cfg)
    opt = optimizer.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                          multi_precision=False)

    def step(x, y):
        loss = crit(model(x), y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    compiled = jit.compile(step, models=[model], optimizers=[opt])
    rng = np.random.RandomState(0)
    ids, lab = (parallel.shard_tensor(paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (batch, seq)).astype("int32")),
        (("dp", "sharding"), None)) for _ in range(2))

    losses = []
    for i in range(steps):
        t0 = time.perf_counter()
        loss = compiled(ids, lab)
        loss._data.block_until_ready()
        losses.append(float(loss))
        # progress only (step 0 is the compile): nothing here is a metric
        print(f"step {i}: loss {losses[-1]:.4f}  "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)

    _require(all(math.isfinite(x) for x in losses), f"loss: {losses}")
    # random weights predict uniformly: the first loss is ln(vocab)
    _require(abs(losses[0] - math.log(cfg.vocab_size)) < 1.0,
             f"first loss {losses[0]} far from ln(vocab)")
    _require(all(b < a for a, b in zip(losses, losses[1:])),
             f"loss not decreasing on a fixed batch: {losses}")
    counts = attention_path_counts()
    _check_paths(counts, need=["attn_kernel"])

    out = {"losses": [round(x, 4) for x in losses], "mesh": mesh or None,
           "attention_paths": counts}
    if n_dev > 1:
        # the arrays really live on every chip of the mesh
        w = model.gpt.blocks.qkv_w._data
        for name, arr in (("qkv_w", w), ("batch", ids._data)):
            _require(len(arr.sharding.device_set) == n_dev,
                     f"{name} on {len(arr.sharding.device_set)} of {n_dev} "
                     "devices")
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in jax.devices()[:n_dev]]
        if None not in in_use:            # the CPU backend reports none
            floor = w.nbytes // (4 * n_dev)
            _require(min(in_use) > floor, f"idle device: {in_use}")
        out["bytes_in_use"] = in_use
        out["qkv_w_sharding"] = str(w.sharding.spec)
    return out


def _post(url, body, timeout):
    import urllib.request

    req = urllib.request.Request(
        url + "/v1/completions", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def _complete(url, prompt, n_new, timeout, stream=False):
    """One /v1/completions round trip -> greedy token ids."""
    body = {"prompt": prompt, "max_tokens": n_new, "stream": stream,
            "deadline_s": timeout}
    with _post(url, body, timeout + 30) as resp:   # non-200 raises
        raw = resp.read().decode("utf-8")
    if not stream:
        choice = json.loads(raw)["choices"][0]
        toks, reason = choice["token_ids"], choice["finish_reason"]
    else:
        toks, reason = [], None
        for event in raw.split("\n\n"):
            if event.startswith("data: ") and event != "data: [DONE]":
                choice = json.loads(event[len("data: "):])["choices"][0]
                toks.extend(choice.get("token_ids") or [])
                reason = choice.get("finish_reason") or reason
    _require(reason == "stop", f"finish_reason {reason!r}")
    _require(len(toks) == n_new, f"{len(toks)} tokens, wanted {n_new}")
    return toks


def serve_leg(args):
    import threading

    import numpy as np
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import jit
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.ops.pallas_ops import (attention_path_counts,
                                           reset_attention_path_counts)
    from paddle_tpu.serving import EngineConfig, LLMEngine
    from paddle_tpu.serving.api import start_api_server

    cfg = _gpt_config(args.tiny)
    n_new = 8 if args.tiny else 64
    # the first request of each shape compiles inside its deadline
    timeout = 900.0

    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    if jax.devices()[0].platform == "tpu":
        model.bfloat16()
    model.eval()
    rng = np.random.RandomState(1)
    # prompt lengths are multiples of 128: flash-eligible prefill
    prompts = {name: rng.randint(0, cfg.vocab_size, (int(name[1:]),)).tolist()
               for name in ("a128", "b256", "c128")}

    def dense_forward(x):
        return model(x)

    dense = jit.compile(dense_forward, models=[model], donate=False,
                        train=False)

    def reference_ranks(prompt, toks):
        """Teacher-forced dense forward over prompt+served tokens: the
        rank of each served token in the reference logits (0 = argmax)."""
        ids = np.asarray([prompt + toks[:-1]], np.int32)
        logits = np.asarray(dense(paddle.to_tensor(ids))._data[0]
                            .astype("float32"))
        rows = logits[len(prompt) - 1:]
        _require(np.isfinite(rows).all(), "non-finite reference logits")
        return [int((row > row[t]).sum()) for row, t in zip(rows, toks)]

    def serve(config, plan):
        reset_attention_path_counts()
        engine = LLMEngine(model, config)
        server = start_api_server(engine=engine, port=0)
        try:
            return plan(server.url), attention_path_counts()
        finally:
            server.stop()

    out = {"requests": 0}

    def fp_plan(url):
        plain = _complete(url, prompts["a128"], n_new, timeout)
        streamed = _complete(url, prompts["a128"], n_new, timeout,
                             stream=True)
        _require(streamed == plain,
                 "streamed tokens differ from non-streamed")
        pair = {}

        def worker(name):
            pair[name] = _complete(url, prompts[name], n_new, timeout)

        threads = [threading.Thread(target=worker, args=(n,))
                   for n in ("b256", "c128")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout + 60)
        _require(set(pair) == {"b256", "c128"},
                 "a concurrent request failed")
        out["requests"] += 4
        return {"a128": plain, **pair}

    served, counts = serve(EngineConfig(), fp_plan)
    # heads of 128 lanes over full-precision pools take the per-head MXU
    # products; the rehearsal's heads of 64 keep the segment body
    d = cfg.hidden_size // cfg.num_attention_heads
    body = "head_products" if d % 128 == 0 else "segment_products"
    _check_paths(counts,
                 need=["attn_kernel", "ragged_kernel",
                       f"ragged_kernel:{body}"],
                 allowed_fallbacks=("ragged_fallback:chunk_gt_1",))
    out["attention_paths"] = counts
    for toks in served.values():
        _require(all(0 <= t < cfg.vocab_size for t in toks), "token id range")
    worst = {}
    for name in ("a128", "c128"):     # one reference shape: 128 + n_new - 1
        toks = served[name]
        ranks = reference_ranks(prompts[name], toks)
        worst[name] = max(ranks)
        print(f"fp {name}: {sum(r == 0 for r in ranks)}/{len(ranks)} tokens "
              f"are the reference argmax, worst rank {max(ranks)}",
              flush=True)
    out["worst_reference_rank"] = worst
    _require(max(worst.values()) < TOP_K,
             f"served tokens off-reference: {worst}")

    def int8_plan(url):
        out["requests"] += 1
        return _complete(url, prompts["a128"], n_new, timeout)

    toks8, counts8 = serve(EngineConfig(kv_cache_dtype="int8", block_size=32),
                           int8_plan)
    _check_paths(counts8,
                 need=["attn_kernel", "ragged_kernel",
                       "ragged_kernel:segment_products"],
                 allowed_fallbacks=("ragged_fallback:chunk_gt_1",))
    ranks8 = reference_ranks(prompts["a128"], toks8)
    agree = sum(a == b for a, b in zip(toks8, served["a128"]))
    print(f"int8 a128: {agree}/{n_new} tokens equal the fp engine's, worst "
          f"reference rank {max(ranks8)}", flush=True)
    _require(max(ranks8) < TOP_K, f"int8 tokens off-reference: {ranks8}")
    out["int8"] = {"attention_paths": counts8,
                   "worst_reference_rank": max(ranks8),
                   "tokens_equal_fp": agree}
    return out


_GROUPED_PATHS = ["attn_kernel:grouped", "ragged_kernel",
                  "ragged_kernel:head_products"]


def _family_models():
    """name -> (model class, its configuration, the reference module, the
    attention paths its programs must take): small models at each family's
    real head geometry."""
    from benchmark.lib import (reference_afmoe, reference_lfm2,
                               reference_mistral4)
    from paddle_tpu.models import (AfmoeConfig, AfmoeForCausalLM,
                                   Lfm2MoeConfig, Lfm2MoeForCausalLM,
                                   Mistral4Config, Mistral4ForCausalLM)

    small = dict(vocab_size=512, hidden_size=256, intermediate_size=512,
                 moe_intermediate_size=128, num_experts=8,
                 num_experts_per_tok=2, num_dense_layers=1,
                 max_position_embeddings=1024, initializer_range=0.05)
    return {
        "afmoe": (AfmoeForCausalLM, AfmoeConfig(
            num_hidden_layers=3, num_attention_heads=48,
            num_key_value_heads=8, head_dim=128, sliding_window=128,
            layer_types=["sliding_attention", "full_attention",
                         "sliding_attention"], **small), reference_afmoe,
                  _GROUPED_PATHS),
        "lfm2": (Lfm2MoeForCausalLM, Lfm2MoeConfig(
            num_hidden_layers=4, num_attention_heads=32,
            num_key_value_heads=8, head_dim=64,
            layer_types=["conv", "full_attention", "conv", "conv"],
            **small), reference_lfm2, _GROUPED_PATHS),
        # the published heads and latent; the original context shrunk to
        # 128 so that the prompts pass it (YaRN's ramp, a_t > 1)
        "mistral4": (Mistral4ForCausalLM, Mistral4Config(
            vocab_size=512, hidden_size=256, num_hidden_layers=2,
            q_lora_rank=128, moe_intermediate_size=128, n_routed_experts=8,
            num_experts_per_tok=2, max_position_embeddings=1024,
            initializer_range=0.05, rope_parameters={
                "rope_type": "yarn", "rope_theta": 10000.0, "factor": 8.0,
                "original_max_position_embeddings": 128, "beta_fast": 32.0,
                "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
                "llama_4_scaling_beta": 0.1}), reference_mistral4,
            ["attn_kernel", "ragged_kernel",
             "ragged_kernel:latent_products"])}


def families_leg(args):
    import dataclasses

    import numpy as np
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas_ops import (attention_path_counts,
                                           reset_attention_path_counts)
    from paddle_tpu.serving import EngineConfig, LLMEngine
    from paddle_tpu.serving.scheduler import SamplingParams

    n_new = 8 if args.tiny else 32
    out = {}
    for name, (cls, cfg, ref, need) in _family_models().items():
        paddle.seed(0)
        model = cls(cfg)
        if jax.devices()[0].platform == "tpu":
            model.bfloat16()
        model.eval()
        rng = np.random.RandomState(2)
        prompts = [rng.randint(0, cfg.vocab_size, (n,)).tolist()
                   for n in (128, 256)]
        reset_attention_path_counts()
        engine = LLMEngine(model, EngineConfig(
            block_size=64, max_num_seqs=4, max_model_len=512))
        served = engine.generate(prompts,
                                 SamplingParams(max_new_tokens=n_new))
        counts = attention_path_counts()
        _check_paths(counts, need=need,
                     allowed_fallbacks=("ragged_fallback:chunk_gt_1",))
        file_like = dataclasses.asdict(cfg)
        file_like["harness"] = {"kwargs": {
            "first_expert": cfg.first_expert,
            "router_experts": cfg.router_experts}}
        params = ref.params_from_model(model)
        worst = 0
        for prompt, seq in zip(prompts, served):
            logits = np.asarray(ref.logits(params, jnp.asarray(seq),
                                           file_like))
            rows = logits[len(prompt) - 1:-1]
            toks = seq[len(prompt):]
            _require(len(toks) == n_new and np.isfinite(rows).all(),
                     f"{name}: {len(toks)} tokens, finite "
                     f"{np.isfinite(rows).all()}")
            ranks = [int((row > row[t]).sum()) for row, t in zip(rows, toks)]
            worst = max(worst, max(ranks))
            print(f"{name} prompt {len(prompt)}: "
                  f"{sum(r == 0 for r in ranks)}/{n_new} tokens are the "
                  f"reference argmax, worst rank {max(ranks)}", flush=True)
        _require(worst < TOP_K, f"{name}: served tokens off-reference, "
                                f"worst rank {worst}")
        out[name] = {"attention_paths": counts,
                     "worst_reference_rank": worst,
                     "cache_groups": list(engine.caches) + list(
                         engine.states)}
    return out


def child_main(args):
    info, stats = _start_child(args)
    result = {"leg": args.leg, "ok": False}
    try:
        result.update({"train": train_leg, "serve": serve_leg,
                       "families": families_leg}[args.leg](args))
        result["ok"] = True
    except Exception as e:   # the leg boundary: report, then fail the child
        import traceback

        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"[:600]
    result.update(info)
    result.update(stats.report())
    print(json.dumps(result), flush=True)
    sys.exit(0 if result["ok"] else 1)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--leg", choices=("train", "serve", "families"),
                    help=argparse.SUPPRESS)    # set by the parent
    args = ap.parse_args()
    if args.leg:
        sys.path.insert(0, ROOT)
        child_main(args)
    else:
        parent_main(args)


if __name__ == "__main__":
    main()
