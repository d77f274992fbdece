"""paddle_tpu.serving — continuous-batching LLM inference on a paged KV
cache (Ragged Paged Attention + MPK-style runtime scheduling; PAPERS.md).

Quickstart::

    from paddle_tpu.serving import LLMEngine, EngineConfig, SamplingParams
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config

    model = GPTForCausalLM(gpt_test_config(stacked_blocks=True))
    engine = LLMEngine(model, EngineConfig(block_size=16))
    outs = engine.generate([prompt_a, prompt_b],
                           SamplingParams(max_new_tokens=32))

Layers (each its own module, each independently testable):

- `kv_cache.BlockKVCache` — block pool + free-list allocator, per-request
  block tables, copy-on-fork, bit-exact eviction swap, and the automatic
  prefix-cache index (chained block keys, LRU-parked unreferenced
  blocks; `prefix_block_keys`).
- `scheduler.Scheduler`  — waiting queue, token-budget admission (with
  longest-cached-prefix adoption), preemption-by-eviction;
  `SamplingParams` / `Request` state machines.
- `spec.propose_ngram`   — stdlib n-gram/prompt-lookup draft proposal
  for speculative decoding (no second model).
- `engine.LLMEngine`     — jitted prefill/decode/sample step programs over
  `ops.ragged_paged_attention` (ONE fixed-shape fused update+attend
  decode program; `ops.paged_attention` holds its XLA fallback and the
  pool writers the prefill program calls), token-for-token equal to the
  dense
  `GPTForCausalLM.generate` (tests/test_serving.py pins it); with
  `EngineConfig(speculative_tokens=k)` a fixed-shape multi-token verify
  program emits several accepted tokens per decode step.
- `router.Router`        — the multi-replica tier (ISSUE 17):
  prefix-cache-aware sticky routing over N engine replicas, optional
  disaggregated prefill/decode (bit-exact KV handoff), drain/failover;
  `replica.ReplicaWorker` is the engine-owning worker half.
- `api.ApiServer`        — the OpenAI-compatible HTTP front door
  (ISSUE 19): /v1/completions + /v1/chat/completions with SSE token
  streaming, API-key → tenant mapping, deadline propagation and
  SLO-aware 429 shedding, over a local engine or the router.

The user-facing entry point also hangs off `paddle_tpu.inference`
(`inference.LLMEngine` etc.), next to the Predictor serving surface.
"""
from .kv_cache import (BlockAllocatorError, BlockKVCache,
                       prefix_block_keys)
from .scheduler import Request, SamplingParams, Scheduler, SchedulerOutput
from .spec import propose_ngram
from .engine import EngineConfig, LLMEngine
from .router import Router, RouterConfig, RpcReplicaClient
from .replica import ReplicaWorker
from .api import ApiServer, start_api_server

__all__ = [
    "ApiServer", "BlockAllocatorError", "BlockKVCache", "EngineConfig",
    "LLMEngine", "ReplicaWorker", "Request", "Router", "RouterConfig",
    "RpcReplicaClient", "SamplingParams", "Scheduler", "SchedulerOutput",
    "prefix_block_keys", "propose_ngram", "start_api_server",
]
