"""`LLMEngine` — continuous-batching inference over a paged KV cache.

The dense path (`GPTForCausalLM.generate`) runs ONE fixed batch to
completion: no admission, no batching across arrivals, O(S_max) cache per
request.  This engine serves an ever-changing request mix through a small
set of jitted step programs of fixed padded shape (XLA compiles one
decode program, and one prefill a prompt length), with the scheduler —
waiting queue, token-budget admission, preemption — living OUTSIDE the
compiled step (the MPK structure from PAPERS.md: runtime scheduling
around static tensor programs).

The engine names no model (ROADMAP D2).  A model hands it a serving form
(`models.serving_form.ServingForm`, from `model.serving_form()`): the
parameter arrays, `embed`, one `layer` step per layer, `logits`, and per
layer a `LayerSpec` (query heads, K/V heads, head size, window, cache
group).  The engine builds the attention each layer step calls and keeps
one `BlockKVCache` per cache group - its own id space, pool shape and block
table; a request holds a table in each, and every program takes one table
and one slot array per group.  A stacked-blocks GPT is one group of full
layers and allocates exactly as before; afmoe (models/afmoe.py) is a
`full` and a `window` group, the second bounded by the window whatever a
sequence's length (kv_cache.py).  A layer that keeps no K/V but a state of
fixed size a sequence names a `StateSpec` instead, and the engine keeps a
`StateCache` per state group beside the K/V groups, a slot a request: every
program takes one slot-index array a state group, a layer's state pool
travels among the donated pools in layer order, and the layer's step is
handed the rows' states and hands back the new ones (lfm2_moe's gated
short convolutions, models/lfm2.py); a spec that asks for it
(`StateSpec.in_place`: a state of megabytes) is handed the pool itself and
the rows' slot indices instead, and updates the rows' slots in it, so no
program gathers or scatters the rows' states (brumby's power retention,
models/brumby.py).  A layer that keeps several memories side by side
names a tuple of specs, and its step is handed one function a spec, in
that order, its pools travelling in the same order (Falcon-H1's attention
beside a Mamba-2 mixer, a K/V group and two state groups a layer,
models/falcon_h1.py).  A model with no such layer builds
no state group and its programs are what they were.  A model whose EVERY
layer names a `StateSpec` has no K/V group at all: `self.cache` is None,
the programs take no table and no slot array, and admission, preemption
and release go by state slots alone (`CacheGroups` over the state groups;
a sequence never grows, so none is ever evicted for room).  A layer of latent
attention names a `LatentSpec`: its cache group keeps ONE pool a layer
(`BlockKVCache(value_in_key=True)`), and what the layer attends in a
whole-prompt prefill - keys and values it expands from the chunk's own
latents, through flash - is not what it stores, so it hands the engine the
rows to store and both forms, and the engine calls the one its program
needs: `whole` in `prefill(P)`, `stored` (absorbed queries against the
pool, `ragged_latent_attention_arrays`) in every program that reads the
pool (mistral4, models/mistral4.py).  Options a family does
not carry (`ServingForm.unsupported`) raise at construction by name.

Step programs (all array-level, weights threaded as inputs):

- ``prefill(P)``   — one request, exact prompt length, causal flash
  attention within the chunk + paged K/V writes.  Exact length (not
  bucketed) on purpose: it makes the prefill arithmetic *identical* to
  the dense path's flash prefill, which is what turns "paged decode
  matches dense generate" from a tolerance into token-for-token equality
  (tests/test_serving.py).  One compile per distinct prompt length — the
  prefill-compile price of exactness; decode, the steady-state loop, is
  ONE fixed-shape program (ragged).  Where every layer of the form is a
  `LayerSpec` (decided once, at construction) the program also CARRIES
  THE DECODE ROWS: the prompt's P tokens and `max_num_seqs - 1` rider
  rows, one token each, run every layer's products as ONE [1, P + B]
  sequence; the attention splits at P, flash over the prompt and the C = 1
  ragged call over the riders (against the pools the prompt has just
  written), and the logits of position P - 1 and of the riders go to ONE
  sampler call of `max_num_seqs` rows, the decode step's.  A rider takes
  its token from the step in flight as a decode step's row does (the
  feed's selection, inside the program).  Rows the scheduler did not
  hand it are padding (`lens` 0), so one request alone compiles the
  program a loaded loop runs.  A form with state, latent or sparse
  layers keeps the prompt alone (`Scheduler.mix_decode`).
- ``ragged(B, 1)`` — the decode program: per layer ONE fused
  `ops.ragged_paged_attention` call writes the new tokens' K/V to their
  slots and attends the ragged batch against the paged pools (int8
  dequant folded into the block loads — no separate
  `quantized_gather_kv_arrays` pass).  B is pinned to ``max_num_seqs``,
  so ONE compiled program serves every batch composition — no
  recompile when the running-request count changes.  Padding rows
  scatter to a dropped slot and their outputs are ignored.
  ``ragged(1, C)`` serves chunked-prefill continuations.
- ``spec_verify(B, k+1)`` — the same ragged body over 1 + k query
  positions a row (`EngineConfig.speculative_tokens`), returning every
  position's greedy token (and position 0's logits for the sampler), so
  the accept is decided in one step.
- ``feed(B)``      — a decode step's upload: the host's input arrays to
  the device, the token of a row that rode the step in flight taken from
  that step's sampler output (see "One step in flight").
- ``sample(B)``    — per-row replication of the dense `_sample_next`
  (greedy argmax / temperature / top-k / top-p + per-request PRNG key
  threading), vmapped so every request reproduces the sampling stream of
  its own solo `generate(seed=...)` call bit-for-bit.

Numerics contract: every op here mirrors the dense path's arithmetic
(same embedding takes, `_stacked_block_body` blocks, `F.layer_norm`
float32 stats, same LM-head einsum, -1e30 masks) so a mixed-length
continuous batch returns exactly the tokens of per-request solo runs.
Scope of the bit-exactness guarantee: it is pinned against the dense
path's masked-softmax DECODE REFERENCE (`cached_attention_arrays`' XLA
branch — the only decode path off-TPU, where the parity tests run).  On
a TPU host the dense oracle may route through the Pallas flash-decode
kernel, whose online-softmax reduction order differs in the last ulp —
there the two paths are mathematically identical but argmax ties can in
principle resolve differently; parity against the reference branch is
the invariant this module maintains.  Assumes AMP autocast is off
(serving is eval-mode; the dense generate path makes the same
assumption).

Monitor wiring (PR-1 StatRegistry): `serving/queue_depth`,
`serving/running`, `serving/waiting`, `serving/blocks_in_use`,
`serving/block_utilization`, `serving/prefill_tokens`,
`serving/decode_tokens` (decode steps' tokens alone),
`serving/prefill_riders` (the running rows a prefill step carried, one
token each), `serving/prefill_tps`, `serving/decode_tps`,
`serving/preemptions`, `serving/requests_finished`, plus
`serving/step_time` histograms labeled by phase (and beside them the
step record, below: `serving/step_wait{phase}`, `serving/host_stalls`,
`serving/host_stall_seconds`).  Goodput:
`serving/padding_waste{kind=rows|tokens}` (padded fraction of the
fixed-shape decode program — rows and tokens diverge under speculative
decoding, where a row carries 1+drafts query positions),
`serving/goodput_tokens_per_s` (generated tokens over TOTAL engine step
wall time, prefill/idle included).  ISSUE 15:
`serving/prefix_hits`/`prefix_hit_tokens`/`prefix_evictions` (prefix
caching, counted by the cache) and
`serving/spec_proposed`/`spec_accepted`/`spec_accept_rate`
(speculative decoding).  ISSUE 28: `serving/kv_blocks_in_use{group}`,
`serving/kv_tokens_live{group}` (keys the decode kernels have to read,
summed over decode steps: a row's length, or `min(length, window)` in a
window group), and what a form's layers count a step
(`ServingForm.stat_counters`; afmoe: `serving/moe_pairs{phase,where=held|
absent}`, `serving/moe_experts_touched{phase}`, `serving/moe_tokens{phase}`,
phase the kind of step), returned by the step's program and read back with
its tokens - no sync of their own; and `serving/kv_block_steps{group}`
(blocks held, summed over decode steps).  ISSUE 32, per state group:
`serving/state_slots_in_use{group}`, `serving/state_slot_steps{group}`
(slots held, summed over decode steps), `serving/state_bytes{group}` (the
pools, set once), and `serving/state_swaps{dir=out|in}` (counted by the
cache).  ISSUE 33: `serving/sampler_steps{path=argmax|categorical|
truncated}`, one a sampler dispatch, by what the batch's rows made the
sample program run (`_sample_program`).

One step in flight (ISSUE 35).  `step()` dispatches the step it has just
scheduled BEFORE it reads back the step dispatched by the call before it:
the device runs step k while the host schedules, prepares and dispatches
k+1, and the host's work between two steps hides under the device's.  The
host has not seen step k's tokens when it dispatches k+1, so:

- a row that rode step k takes its input token and its sampling key from
  k's sampler ON THE DEVICE (`_feed_sources`: a host-made source index a
  row into the sampler's outputs, which are padded to `max_num_seqs` rows
  whether a prefill's one row or a decode batch made them; -1 = from the
  host).  The ("feed", B) program selects the tokens as it uploads the
  model program's inputs; the ("sample", B) program selects the keys.
  One shape of each whatever the mix, so one request alone compiles all;
- a request with a token OWED (`Request.owed`) is one position longer
  (`total_len`) for the scheduler and `_decode_inputs`, and is not
  decoded again when the owed token is its last by count.  A row that
  ends on its `eos_token_id` is found out a step late: it has ridden
  k+1, that token is dropped at readback (`_emit`), and its blocks, freed
  at k's retire, are written by whoever gets them BEHIND k+1 (device
  programs run in dispatch order);
- whatever needs a request's tokens, key or blocks as they are settles
  the step in flight first (`_settle`: readback, emit, retire, nothing
  new dispatched): `request_output`, `export_request`, `fork_request`,
  `release_request` (cancel, deadline) of a row that owes, any eviction
  the scheduler decides (`StepOwed`), an `idle` decision, the public
  `settle()`, the end of `generate()`.  `adopt_request` and shedding
  touch only requests that have never ridden a step here and settle
  nothing;
- with `speculative_tokens > 0` the drafts of step k+1 come from tokens
  the host must have seen: every step is settled where it is dispatched
  (the loop's depth is 0, not 1; the host then holds every token and the
  decode program takes its inputs straight from it, no feed program).
  Chunked-prefill continuations and prefix registration depend on no
  sampled token and stay in flight.

`serving/steps_dispatched{in_flight=1|0}` counts program steps by whether
an earlier step was still owed at dispatch, `serving/settles{why=spec|
preempt|release|export|fork|idle|drain}` each time the pipeline ran empty.

Host phases (monitor.trace.phase): every boundary of `step()` is one
phase, in this order, and the API pump adds two of its own around it:

    api/drain_submits       the pump's _drain_submits, blocking get included;
                            add_request touches the device only for a
                            sampling request's key (read once, there)
    api/push_progress       _push_engine_progress: a queue.put per stream
    engine/schedule         (step k+1) deadline sweep, shedding,
                            scheduler.schedule(), preemption counts
    engine/prepare          (k+1) the step's inputs as host arrays (tokens,
                            positions, lengths; a table and a slot array a
                            cache group, slots by array arithmetic; a spec
                            step's n-gram drafts), their upload (_run: one
                            crossing; a decode step's goes through the feed
                            program), the model program's dispatch,
                            _store_kv
    engine/sample_dispatch  (k+1) the sampler's five host arrays and the
                            rows' source indices (the keys are host words
                            or stay on the device: nothing is read for
                            them), the path they select (counted; the
                            program branches on the same arrays: no sort
                            for greedy or untruncated rows, one where a row
                            truncates) and its dispatch, the second and
                            last upload
    engine/readback         (step k) _to_host of (tokens, keys[, greedy]
                            [, stats]) in one call: the only device-to-host
                            wait, for a step dispatched a call ago with
                            k+1 already queued behind it
    engine/emit             (k) per row, host only: the new key (a view of
                            the array read back), record_token, TTFT/TPOT
                            (spec: acceptance and the table roll-back)
    engine/retire           (k) retire_finished, _finish_request, the SLO
                            tick, the step's counters and gauges

What a phase may wait on, beside the GIL (the pump's clients and the
HTTP handlers are threads of this process), and whether it counts into
the benchmark's `host_cpu_share.serve` - the six that should never wait
do; their `serving/host_cpu{phase}` over `serving/host_time{phase}`:sum
is the share of the phase the thread RAN:

    phase                   counts  may wait on
    api/drain_submits       no      its queue: a blocking get of up to
                                    poll_s, by design; the device, once,
                                    for a sampling request's key
    api/push_progress       yes     nothing
    engine/schedule         yes     nothing of its own (a settle it
                                    decides, `preempt` or `release`, runs
                                    its readback, emit and retire INSIDE
                                    it, each under its own name too)
    engine/prepare          yes     the runtime: its jitted calls return
                                    before the device runs them, but a
                                    call over the donated pools can block
                                    for a buffer or the dispatch queue
    engine/sample_dispatch  yes     the same, one call
    engine/readback         no      the device, for step k: by design,
                                    and `serving/step_wait` is this wait
    engine/emit             yes     nothing
    engine/retire           yes     nothing (the request log's write and
                                    the SLO tick, where those are on)

Each phase is counted once a program step: a call that dispatches onto an
empty pipeline has no back half, and a call with nothing runnable (nobody
waits, every running row owes its last token: `Scheduler.has_runnable`)
reads the step in flight back without a scheduling pass.

The step record (ISSUE 36).  With a step in flight a step costs the
LONGER of two sides, the device's and the host's, and
`serving/step_time{phase}` (readback to readback) is that maximum.  Where
it is observed (`_observe_step`, once a program step, under the kind of
the step read back) the engine observes beside it
`serving/step_wait{phase=prefill|decode}`: the seconds the thread was
blocked in `_to_host` for that step, 0 for a prefill chunk that sampled
nothing - the device's lead over the host.  `step_time - step_wait` is the
host's side of the step: emit and retire of the step before, the pump,
schedule, prepare and sample_dispatch of the step after, and every
microsecond between them that no phase names.  Wait near 0: the host sets
the pace; wait a large share of the step: the device does.  A host side
over `_HOST_STALL_S` (0.25 s) counts one `serving/host_stalls`, adds
itself to `serving/host_stall_seconds` and leaves a `host_stall` note
(phase, rows, host_s, wait_s) in the flight ring; a first call's compile
is such a stall, and says so.  An idle call observes none of these.
In steady state the device has work queued through every phase: while the
host is in schedule, prepare and sample_dispatch step k runs, and when
readback returns k+1 is already running.  The phases' sum is what the
host is BUSY a step, not a gap; the device idles only where that sum
exceeds its own time a step, or where a settle empties the pipeline.  A
step crosses to the device a fixed number of times, whatever the batch
holds: a decode step twice up (model inputs, sampler inputs) and once
down; a prefill step once up, and once more up and once down when it
samples the first token (`serving/device_calls{dir=h2d|d2h}`, counted by
`_run` / `_to_host`; a call of `step()` makes the uploads of the step it
dispatches and the readback of the one before).
Gates: PTPU_MONITOR (default on) puts each duration into
`serving/host_time{phase}` and the thread's own CPU seconds inside it
(`time.thread_time`) into `serving/host_cpu{phase}`, nothing synced for
either; an open profiler
session gets a host event `ptpu:<phase>` on the device operations' clock
(the programs are named for that view: prefill_<len>, ragged_decode,
ragged_prefill_<c>, spec_verify, feed, sample); PTPU_TRACE=1 adds a
`serving/step` span per `step()` call (`phase`, `rows`, the riders'
`trace_ids` - of the step it DISPATCHES - `state_slots` where the
model has state groups, and `wait_ms` / `host_ms` of the step it READS
BACK: what the step record counts), the phases its children, filed under every
rider's trace; the readback, emit and retire inside it are of the step
before.  A request's `serving/prefill` / `serving/decode_step` span runs
from its step's dispatch to its tokens' emit.

Observability v2 (monitor.trace): with PTPU_TRACE=1 every request gets a
trace — root `serving/request` span with `serving/queue_wait`,
`serving/prefill` (one per chunk), `serving/decode_step` (their `step`
names the `serving/step` they rode) and those `serving/step` children —
readable via `request_trace(rid)`, `/traces/<id>` on the live endpoint
(`EngineConfig(metrics_port=...)`), or `trace.export_chrome_trace()`.
Per-request latency decomposes into `serving/ttft` (arrival → first
token) and `serving/tpot` (inter-token) histograms, recorded whenever
the monitor is on (tracing not required); `serving/compiles{kind}`
counts step-program cache misses.

Request plane (ISSUE 16): `serving/queue_wait` (arrival → first
compute, monitor-gated — visible with tracing off) and
`serving/finish_reason{reason}` (stop/abort/deadline/released/migrated/
shed — the SLO error_rate numerator; "migrated" = handed off to another
replica and "shed" = dropped by SLO admission control, both counted
good) land alongside ttft/tpot; at finish the engine
emits ONE wide `monitor.reqlog` event per request (release time), ticks
`monitor.slo`'s burn-rate engine each step, stamps the request's
trace_id as a histogram exemplar on its ttft/tpot/queue_wait
observations, and marks SLO-violating traces `keep=True` for
tail-based sampling.  All default-off.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from .. import monitor
from ..monitor import trace as mtrace
from ..monitor import perf as mperf
from ..monitor import reqlog as mreqlog
from ..monitor import slo as mslo
from ..monitor import memory as mmem
from ..resilience import faults
from ..resilience.retry import Deadline
from ..ops.paged_attention import (latent_cache_update_arrays,
                                   paged_cache_update_arrays,
                                   quantized_cache_update_arrays)
from ..ops.ragged_paged_attention import (ragged_latent_attention_arrays,
                                          ragged_paged_attention_arrays)
from ..models.serving_form import LayerSpec, StateSpec
from .kv_cache import (BlockAllocatorError, BlockKVCache, CacheGroups,
                       StateCache, prefix_block_keys)
from .scheduler import (Request, SamplingParams, Scheduler,
                        SchedulerOutput, StepOwed, priority_rank,
                        should_shed, worst_fast_burn)
from .spec import propose_ngram

__all__ = ["EngineConfig", "LLMEngine"]

_NEG_INF = -1e30
# a program step whose host side (step_time - step_wait) is longer than
# this is a stall (`serving/host_stalls`): fifteen times the longest host
# side any benchmark cell has shown (17 ms); a constant, on purpose
_HOST_STALL_S = 0.25


def _sampler_path(ds, topk, topp):
    """Which side of `_sample_program` a batch takes, from its rows'
    parameters (numpy on the host, traced in the program): 0 no row
    samples, 1 rows sample and none truncates, 2 a sampling row asks for
    top-k or top-p.  `top_p` on a greedy row (the API passes a body's
    through) truncates nothing."""
    truncates = ds & ((topk > 0) | (topp < 1.0))
    return ds.any().astype(np.int32) + truncates.any().astype(np.int32)


_SAMPLER_PATHS = ("argmax", "categorical", "truncated")


def _sample_program(logits, keys, ds, temp, topk, topp):
    """The ("sample", B) program: a token and a next key for every row of
    [B, V] fp32 logits.  A row's pair is what `models.gpt._sample_next`
    gives on that row alone, bit for bit, so a request reproduces its solo
    generate() stream.  What runs is decided once for the whole batch, by
    `_sampler_path` over the rows' parameters:

        argmax       no row samples: the argmax, the keys as they came
        categorical  + a key split, logits / temperature, the draw
        truncated    + ONE descending sort a row, for top-k and top-p both

    The switch sits outside the `vmap`: on a row's own predicate it would
    lower to a select that runs every side."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def row(truncated, l, key_, t, k, p):
        new_key, sub = jax.random.split(key_)
        ll = l[None, :] / jnp.maximum(t, jnp.float32(1e-6))
        if truncated:
            v = ll.shape[-1]
            # sorted once: the top-k mask is monotone in the value, so
            # masking the sorted row IS sorting the masked row.  Values
            # alone, so stability orders nothing; asked for, the chip
            # sorts an index beside them at twice the time
            desc = jnp.sort(ll, axis=-1, stable=False)[:, ::-1]
            kth = jnp.take_along_axis(
                desc, jnp.clip(k - 1, 0, v - 1)[None, None], axis=-1)
            ll = jnp.where(k > 0, jnp.where(ll < kth, _NEG_INF, ll), ll)
            desc = jnp.where(
                k > 0, jnp.where(desc < kth, _NEG_INF, desc), desc)
            probs = jax.nn.softmax(desc, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep = cum - probs <= p
            thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                             keepdims=True)
            ll = jnp.where(p < 1.0,
                           jnp.where(ll < thresh, _NEG_INF, ll), ll)
        samp = jax.random.categorical(sub, ll, axis=-1).astype(jnp.int32)[0]
        return samp, new_key

    def draw(truncated):
        samp, new_keys = jax.vmap(functools.partial(row, truncated))(
            logits, keys, temp, topk, topp)
        return (jnp.where(ds, samp, greedy),
                jnp.where(ds[:, None], new_keys, keys))

    return jax.lax.switch(
        _sampler_path(ds, topk, topp),
        [lambda: (greedy, keys), functools.partial(draw, False),
         functools.partial(draw, True)])


@dataclasses.dataclass
class _Flight:
    """A program step that was dispatched and that the host has not read
    back: what `_finish` needs to emit its tokens and to time it."""

    kind: str                  # "prefill" | "decode"
    rows: list                 # the requests it sampled a token for
    arrays: Optional[tuple]    # device (tokens, keys, stats, greedy), the
    #                            first two padded to max_num_seqs rows;
    #                            None: a prefill chunk that sampled nothing
    tokens: int                # a prefill's chunk length
    t_begin: float             # the readback before it returned (or, with
    #                            nothing in flight then, its step() began)
    drafts: Optional[list] = None      # a verify step's, a list a row
    spans: tuple = ()          # the rows' serving/prefill|decode_step spans

    def __post_init__(self):
        self.row_of = {r.req_id: i for i, r in enumerate(self.rows)}


@dataclasses.dataclass
class EngineConfig:
    block_size: int = 16
    num_blocks: Optional[int] = None       # default: dense-equivalent pool
    max_num_seqs: int = 8
    # prefill token budget per step; None = whole prompt in one chunk
    # (the exact-parity path — chunked prefill is mathematically equal
    # but reassociates float reductions)
    max_num_batched_tokens: Optional[int] = None
    max_model_len: Optional[int] = None    # default: max_position_embeddings
    # "int8" stores the KV pools as int8 codes + per-block-per-head
    # scales (paddle_tpu.lowbit): same pool BYTES hold ~2× (bf16) / ~4×
    # (fp32) the blocks, at a documented decode tolerance vs fp — see
    # tests/test_lowbit.py.  None = full-precision pools (exact parity).
    kv_cache_dtype: Optional[str] = None
    # launch monitor.serve's live endpoint (/metrics, /healthz,
    # /traces/<id>) on this port when the engine boots; 0 = ephemeral
    # (read it back from engine.metrics_server.port), None = no server.
    metrics_port: Optional[int] = None
    # ISSUE 15 (a): automatic prefix caching — index full KV blocks by
    # chained content keys as prefill fills them; new requests adopt
    # their longest cached prefix by refcount bump and prefill only the
    # uncached tail (N requests sharing a system prompt pay its prefill
    # once).  Unreferenced prefix blocks park on an LRU and are
    # reclaimed last.  Default OFF (finished requests then pin pool
    # blocks in the index, which changes the blocks_in_use==0-at-idle
    # invariant suites pin).
    enable_prefix_caching: bool = False
    # ISSUE 15 (b): speculative decoding — k n-gram/prompt-lookup draft
    # tokens per greedy row, verified in ONE fixed-shape ragged
    # (max_num_seqs, k+1) multi-token program; the longest matching
    # greedy run (plus the correction token) is accepted per step.
    # Token-identical to dense greedy generate(); sampling rows get no
    # drafts (their PRNG stream is preserved exactly — documented
    # scope).  0 = off.
    speculative_tokens: int = 0
    # n-gram proposer knobs: longest/shortest suffix n-gram tried, and
    # how far back the per-row host scan looks
    spec_ngram_max: int = 3
    spec_ngram_min: int = 1
    spec_lookup_window: int = 1024


class LLMEngine:
    """add_request() / step() / generate() over a model's serving form."""

    def __init__(self, model, config: Optional[EngineConfig] = None):
        if not hasattr(model, "serving_form"):
            raise ValueError(
                f"LLMEngine serves models that hand it a serving form "
                f"(`serving_form()`, models/serving_form.py); "
                f"{type(model).__name__} has none")
        form = model.serving_form()
        self.model = model
        model.eval()
        self.form = form
        self.cfg = model.cfg
        self.config = config or EngineConfig()
        c = self.config
        self.max_model_len = int(c.max_model_len
                                 or form.max_position_embeddings)
        # gathered view width mirrors the dense ring rounding
        # (init_caches: length rounds up to 128) so the decode softmax
        # reduces over the SAME padded extent as the dense oracle
        ring = -(-self.max_model_len // 128) * 128
        self.blocks_per_seq = -(-ring // c.block_size)
        # a layer names one spec, or a tuple of them when it keeps several
        # memories side by side (`ServingForm.layer_specs`); `specs` is
        # every spec in layer order, a tuple's in its own order
        self._layer_specs = [tuple(s) if isinstance(s, tuple) else (s,)
                             for s in form.layer_specs]
        specs = [s for layer in self._layer_specs for s in layer]
        # cache groups, in the order the specs first name them; a spec's
        # pools are entry `_layer_slot[i]` of its group's cache
        self._groups: dict = {}          # K/V groups: name -> [LayerSpec]
        state_groups: dict = {}          # state groups: name -> [StateSpec]
        self._layer_slot = []
        for spec in specs:
            kind = (state_groups if isinstance(spec, StateSpec)
                    else self._groups)
            layers = kind.setdefault(spec.group, [])
            self._layer_slot.append(len(layers))
            layers.append(spec)
        if not self._groups and not state_groups:
            raise ValueError(
                f"{type(model).__name__}'s serving form names no layer")
        shared = sorted(set(self._groups) & set(state_groups))
        if shared:
            raise ValueError(
                f"a K/V group and a state group share a name: {shared}")
        # full groups first: `self.cache` is a full group where one exists
        self._groups = dict(sorted(
            self._groups.items(), key=lambda kv: kv[1][0].window is not None))
        for name, layers in self._groups.items():
            if len({(s.pool_row(), s.window) for s in layers}) != 1:
                raise ValueError(
                    f"cache group {name!r}: its layers must share a kind, "
                    "K/V heads, head size and window")
        if c.kv_cache_dtype not in (None, "int8"):
            raise ValueError(
                f'kv_cache_dtype must be None or "int8", got '
                f'{c.kv_cache_dtype!r}')
        self._kv_quant = c.kv_cache_dtype
        wdtype = form.dtype
        self.prefix_caching = bool(c.enable_prefix_caching)
        self.spec_tokens = max(0, int(c.speculative_tokens))
        on = {"kv_cache_dtype": self._kv_quant,
              "speculative_tokens": self.spec_tokens,
              "enable_prefix_caching": self.prefix_caching}
        asked = dict(on, max_num_batched_tokens=c.max_num_batched_tokens)
        # with no K/V group there is no block to share, quantise or roll
        # back, whatever the form says
        for option in (form.unsupported if self._groups else on):
            if asked.get(option):
                raise ValueError(
                    f"EngineConfig.{option}={asked[option]!r} is not carried "
                    f"to {type(model).__name__} yet (its serving form "
                    "lists it as unsupported); leave it off")
        sizes = self._pool_sizes(wdtype)
        self.caches = {}
        for name, layers in self._groups.items():
            heads, lanes, pools = layers[0].pool_row()
            self.caches[name] = BlockKVCache(
                len(layers), sizes[name], c.block_size, heads, lanes,
                dtype=wdtype, kv_quant=self._kv_quant,
                window=layers[0].window, name=name, value_in_key=pools == 1,
                index_lanes=layers[0].index_lanes)
        # the first group's cache: the only one of a one-group model, None
        # for a model with no attention layer
        self.cache = next(iter(self.caches.values()), None)
        # a slot a running sequence, and the dropped slot of padding rows
        self.states = {}
        for name, layers in state_groups.items():
            if len({(tuple(s.shape), s.dtype) for s in layers}) != 1:
                raise ValueError(f"state group {name!r}: its layers must "
                                 "share a shape and a dtype")
            self.states[name] = StateCache(
                len(layers), c.max_num_seqs, layers[0].shape,
                layers[0].dtype or wdtype, name=name)
        # what the scheduler allocates from: all groups or none
        self.kv = (self.cache if len(self.caches) == 1 and not self.states
                   else CacheGroups({**self.caches, **self.states}))
        if monitor.enabled() and self.cache is not None:
            num_blocks = self.cache.num_blocks
            monitor.gauge("lowbit/kv_blocks",
                          "paged KV pool size in blocks").labels(
                dtype=self._kv_quant or str(wdtype)).set(num_blocks)
            if self._kv_quant:
                # what THIS pool's block count would have cost at the
                # model dtype, minus what the quantized pool costs
                spec = specs[0]
                fp_cost = num_blocks * len(specs) * BlockKVCache.block_bytes(
                    c.block_size, spec.num_kv_heads, spec.head_dim, wdtype)
                monitor.counter("lowbit/bytes_saved").labels(
                    wing="kv_cache").add(max(0, fp_cost
                                             - self.cache.pool_bytes))
        # a whole prompt's prefill carries the decode rows where every
        # layer is plain K/V attention alone (module docstring, `prefill(P)`)
        self._mix = (all(isinstance(s, LayerSpec) for s in form.layer_specs)
                     and not self.spec_tokens and c.max_num_seqs > 1)
        self.scheduler = Scheduler(
            self.kv, max_num_seqs=c.max_num_seqs,
            max_num_batched_tokens=(c.max_num_batched_tokens
                                    or self.max_model_len),
            spec_tokens=self.spec_tokens,
            max_model_len=self.max_model_len, mix_decode=self._mix)
        self._requests: dict = {}
        self._next_id = 0
        self._jit_cache: dict = {}
        # monitor handles (cheap no-ops when PTPU_MONITOR=0)
        m = monitor
        self._m_queue = m.gauge("serving/queue_depth",
                                "requests waiting for admission")
        self._m_running = m.gauge("serving/running", "requests decoding")
        self._m_waiting = m.gauge("serving/waiting",
                                  "waiting incl. preempted")
        self._m_blocks = m.gauge("serving/blocks_in_use", "KV blocks held")
        self._m_util = m.gauge("serving/block_utilization",
                               "blocks_in_use / num_blocks")
        self._m_pre_toks = m.counter("serving/prefill_tokens")
        self._m_dec_toks = m.counter("serving/decode_tokens")
        self._m_riders = m.counter(
            "serving/prefill_riders",
            "running rows a prefill step carried, one token each")
        self._m_pre_tps = m.gauge("serving/prefill_tps")
        self._m_dec_tps = m.gauge("serving/decode_tps")
        self._m_preempt = m.counter("serving/preemptions")
        self._m_done = m.counter("serving/requests_finished")
        self._m_expired = m.counter("serving/deadline_expired",
                                    "requests aborted past deadline_s")
        self._m_step = m.histogram("serving/step_time")
        # ISSUE 36, the step record (module docstring)
        self._m_step_wait = m.histogram(
            "serving/step_wait",
            "seconds the engine's thread was blocked reading a program "
            "step back, by the step's kind (phase=prefill|decode)")
        self._m_stalls = m.counter(
            "serving/host_stalls",
            "program steps whose host side (step_time - step_wait) was "
            "over 0.25 s")
        self._m_stall_s = m.counter(
            "serving/host_stall_seconds",
            "the host side of those steps, summed")
        self._m_ttft = m.histogram("serving/ttft",
                                   "arrival to first token, seconds")
        self._m_tpot = m.histogram("serving/tpot",
                                   "inter-token latency after the first, "
                                   "seconds")
        # ISSUE 16 request plane: queue wait as a histogram (the PR-5
        # queue_wait SPAN needs tracing on; this is visible with just
        # the monitor), and the completion mix the slo error_rate reads
        self._m_queue_wait = m.histogram(
            "serving/queue_wait",
            "arrival to first prefill compute, seconds")
        self._m_finish = m.counter(
            "serving/finish_reason",
            "finished requests by outcome "
            "(stop|abort|deadline|released|migrated|shed)")
        # ISSUE 19 multi-tenant breakdowns: tenant-labeled counters.
        # Label children materialize only for requests that CARRY a
        # tenant — default-pool traffic exports zero new series.
        self._m_tenant_tokens = m.counter(
            "serving/tenant_tokens", "generated tokens by tenant")
        self._m_tenant_admitted = m.counter(
            "serving/tenant_admitted", "requests accepted by tenant")
        self._m_tenant_shed = m.counter(
            "serving/tenant_shed",
            "best-effort requests shed by SLO admission control, "
            "by tenant")
        self._m_compiles = m.counter("serving/compiles",
                                     "step-program cache misses")
        # goodput: how much of the fixed-shape decode program is padding
        pad = m.gauge(
            "serving/padding_waste",
            "padded fraction of the fixed-shape decode program")
        self._m_pad_rows = pad.labels(kind="rows")
        self._m_pad_toks = pad.labels(kind="tokens")
        self._m_goodput = m.gauge(
            "serving/goodput_tokens_per_s",
            "generated tokens per second of total engine step wall "
            "time (prefill/idle/scheduling included)")
        # ISSUE 15 (b): speculative decoding observability — proposed vs
        # accepted draft tokens, and their cumulative ratio
        self._m_spec_prop = m.counter(
            "serving/spec_proposed", "draft tokens proposed")
        self._m_spec_acc = m.counter(
            "serving/spec_accepted", "draft tokens accepted by verify")
        self._m_spec_rate = m.gauge(
            "serving/spec_accept_rate",
            "cumulative accepted/proposed draft-token ratio")
        # ISSUE 20 memory microscope: free/parked gauges fed from the
        # cache's ONE counts() source (satellite: blocks_in_use /
        # block_utilization / the admission view can no longer drift),
        # tenant-labeled capacity attribution (children materialize
        # only for tenant-carrying requests, like the other tenant
        # metrics), and the per-step memobs state (PTPU_MEMOBS-gated)
        self._m_kv_free = m.gauge(
            "serving/kv_free_blocks",
            "truly free KV blocks (free list only, parked excluded)")
        self._m_kv_parked = m.gauge(
            "serving/kv_parked_blocks",
            "LRU-parked prefix blocks (adoptable AND reclaimable)")
        self._m_tenant_kv = m.gauge(
            "serving/kv_blocks_held", "KV blocks held, by tenant")
        self._m_tenant_kv_peak = m.gauge(
            "serving/kv_blocks_peak_share",
            "peak fraction of the KV pool held, by tenant")
        # ISSUE 28: per cache group (blocks held now; keys the decode
        # kernels had to read and blocks held, both summed over decode
        # steps), and what a form's layers count a step, by kind of step
        per_group = (
            m.gauge("serving/kv_blocks_in_use",
                    "KV blocks held, by cache group"),
            m.counter("serving/kv_tokens_live",
                      "keys the decode kernels had to read, by cache group"),
            m.counter("serving/kv_block_steps",
                      "KV blocks held, summed over decode steps, by cache "
                      "group"))
        self._m_group = [tuple(x.labels(group=g) for x in per_group)
                         for g in self.caches]
        # ISSUE 32: per state group (slots held now, and summed over
        # decode steps; the pools' bytes, which never change)
        per_state = (
            m.gauge("serving/state_slots_in_use",
                    "state slots held, by state group"),
            m.counter("serving/state_slot_steps",
                      "state slots held, summed over decode steps, by "
                      "state group"))
        self._m_state = [tuple(x.labels(group=g) for x in per_state)
                         for g in self.states]
        for g, st in self.states.items():
            m.gauge("serving/state_bytes",
                    "bytes of a state group's pools").labels(
                group=g).set(st.pool_bytes)
        calls = m.counter(
            "serving/device_calls",
            "host/device crossings of the engine (_run, _to_host): "
            "a decode step makes 2 h2d and 1 d2h whatever the batch holds")
        self._m_h2d = calls.labels(dir="h2d")
        self._m_d2h = calls.labels(dir="d2h")
        # ISSUE 35: the step in flight.  `step()` dispatches the step it
        # has scheduled before it reads this one back; a verify step's
        # drafts come from tokens the host must have seen, so with
        # speculation on every step is read back where it was dispatched
        self._flight: Optional[_Flight] = None
        self._retired: list = []     # what the step() under way returns
        self._step_span = None       # its serving/step span (PTPU_TRACE=1)
        self._settle_each_step = self.spec_tokens > 0
        dispatched = m.counter(
            "serving/steps_dispatched",
            "program steps by whether an earlier step's results were "
            "still owed at dispatch (in_flight=1|0)")
        self._m_dispatched = [dispatched.labels(in_flight=str(i))
                              for i in (0, 1)]
        self._m_settles = m.counter(
            "serving/settles",
            "times the step in flight was read back with nothing "
            "dispatched behind it "
            "(why=spec|preempt|release|export|fork|idle|drain)")
        # what a step feeds from when nothing is in flight: never read
        # (every row's source index says host), device-resident so that
        # it is not uploaded
        self._no_toks = jnp.zeros((c.max_num_seqs,), jnp.int32)
        self._no_keys = jnp.zeros((c.max_num_seqs, 2), jnp.uint32)
        steps = m.counter(
            "serving/sampler_steps",
            "sampler dispatches by what the batch made the program run "
            "(argmax|categorical|truncated)")
        self._m_sampler = [steps.labels(path=p) for p in _SAMPLER_PATHS]
        self._m_stats = {
            # ptpu-check[metric-hygiene]: names and labels are the form's `stat_counters`: literals in the model's file
            phase: [m.counter(name).labels(phase=phase, **labels)
                    for name, labels in form.stat_counters]
            for phase in ("prefill", "decode")}
        # every spec's (cache, index in it), in layer order
        self._layer_pools = [
            ((self.states if isinstance(spec, StateSpec)
              else self.caches)[spec.group], i)
            for spec, i in zip(specs, self._layer_slot)]
        self._tenant_kv_peak: dict = {}
        self._storm = mmem.StormDetector()
        self._memobs_prev = {"evict": 0, "swap_in": 0}
        self._spec_proposed_total = 0
        self._spec_accepted_total = 0
        self._wall_s_total = 0.0
        self._goodput_toks = 0
        # rid -> trace_id survives release_request (the spans live in the
        # bounded monitor.trace store, not on the request); bounded like
        # that store — entries past it map to evicted traces anyway, and
        # an unbounded dict would leak one entry per request served
        from collections import OrderedDict

        self._trace_ids: "OrderedDict" = OrderedDict()
        self.metrics_server = None
        if c.metrics_port is not None:
            from ..monitor import serve as mserve

            self.metrics_server = mserve.start_server(c.metrics_port)

    def _pool_sizes(self, wdtype) -> dict:
        """Blocks per cache group.  `num_blocks` sizes the full groups (a
        window group too, when it is the smaller); the default is the
        dense-equivalent pool, `max_num_seqs` whole sequences, and for a
        window group `max_num_seqs * (ceil(window / block_size) + 1)`, all
        a sequence ever holds there.  A default that the device cannot
        hold raises before anything is allocated."""
        c = self.config
        bs = c.block_size
        sizes, asked = {}, 0
        for name, layers in self._groups.items():
            spec = layers[0]
            fp_blocks = c.max_num_seqs * self.blocks_per_seq
            if spec.window is not None:
                fp_blocks = min(fp_blocks, c.max_num_seqs
                                * (-(-spec.window // bs) + 1))
            heads, lanes, pools = spec.pool_row()
            per_block = len(layers) * BlockKVCache.block_bytes(
                bs, heads, lanes, wdtype, pools=pools,
                index_lanes=spec.index_lanes)
            if c.num_blocks is not None:
                n = (c.num_blocks if spec.window is None
                     else min(c.num_blocks, fp_blocks))
            elif self._kv_quant:
                # same BYTE budget as the full-precision default pool —
                # the whole point: halved/quartered bytes/block ⇒ ~2–4×
                # blocks, fewer preemptions under the same memory ceiling
                n = fp_blocks * per_block // (
                    len(layers) * BlockKVCache.block_bytes(
                        bs, heads, lanes, wdtype, self._kv_quant))
            else:
                n = fp_blocks
                asked += n * per_block
            sizes[name] = n
        limit = self._device_bytes()
        if asked and limit and asked > limit:
            raise ValueError(
                f"the default KV pools ask for {asked:,} bytes "
                f"({c.max_num_seqs} sequences of max_model_len "
                f"{self.max_model_len}) and the device holds {limit:,}: "
                "set EngineConfig.max_model_len to the longest request "
                "served, or EngineConfig.num_blocks to the pool size")
        return sizes

    @staticmethod
    def _device_bytes() -> int:
        """The device's memory, 0 where the backend does not say (CPU)."""
        stats = jax.devices()[0].memory_stats() or {}
        return int(stats.get("bytes_limit", 0))

    # -- request API --------------------------------------------------------

    def add_request(self, prompt_ids, sampling_params=None) -> int:
        params = sampling_params or SamplingParams()
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        if not prompt:
            raise ValueError("empty prompt")
        total = len(prompt) + params.max_new_tokens
        if total > self.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({params.max_new_tokens}) exceeds max_model_len "
                f"({self.max_model_len})")
        req = Request(self._next_id, prompt, params)
        self._next_id += 1
        req.key = self._init_key(params)
        if params.deadline_s is not None:
            req.deadline = Deadline(params.deadline_s)
        if self.prefix_caching:
            # chained content keys over the prompt's full blocks: the
            # scheduler matches/adopts against them at admission and
            # _prefill_body registers newly-filled blocks under them
            req.prefix_keys = prefix_block_keys(prompt,
                                                self.cache.block_size)
        self._begin_trace(req)
        self._requests[req.req_id] = req
        self.scheduler.add(req)
        if monitor.enabled() and params.tenant:
            self._m_tenant_admitted.labels(tenant=params.tenant).inc()
        return req.req_id

    def fork_request(self, parent_id, sampling_params=None) -> int:
        """Copy-on-fork: a new request continuing the parent's current
        text, SHARING the parent's KV blocks (refcounted; first divergent
        write copies only the shared partial block).  The shared-prompt
        serving shape: N samplings of one prompt pay its prefill once."""
        parent = self._requests[parent_id]
        if parent.owed:          # the text to continue, all of it
            self._settle("fork")
        if parent.state not in (Request.RUNNING,) or not parent.prefill_done:
            raise ValueError(
                "fork requires a running, fully-prefilled parent")
        params = sampling_params or parent.params
        prompt = parent.prompt_ids + parent.output_ids
        total = len(prompt) + params.max_new_tokens
        if total > self.max_model_len:
            raise ValueError("forked request exceeds max_model_len")
        req = Request(self._next_id, prompt, params)
        self._next_id += 1
        req.key = self._init_key(params)
        if params.deadline_s is not None:
            req.deadline = Deadline(params.deadline_s)
        # parent has written total_len-1 positions (the last sampled token
        # is fed next step); the child re-feeds it as its final "prompt"
        # token through its own prefill continuation
        req.num_computed = parent.total_len - 1
        self.kv.fork(parent_id, req.req_id)
        # that re-feed WRITE lands at position total_len-1, which lives in
        # the (shared) last block — privatize it now so the child's
        # recomputation can never perturb the parent's cache
        self.kv.privatize_last_block(req.req_id)
        self._begin_trace(req, forked_from=parent_id)
        self._requests[req.req_id] = req
        self.scheduler.add(req)
        return req.req_id

    def export_request(self, req_id) -> dict:
        """Detach a RUNNING, fully-prefilled request for migration to
        another engine (ISSUE 17 disaggregated prefill→decode): returns
        its prompt, tokens emitted so far, the row's evolved PRNG key,
        and the bit-exact host KV snapshot (`BlockKVCache.swap_out` —
        the preemption swap path, so restore is bit-identical and the
        local blocks are freed).  The local request finishes with
        reason "migrated".  `adopt_request` on the receiving engine is
        the inverse; the pair is token-identical to never migrating
        (greedy and seeded sampling alike — the shipped key IS the
        row's sampling stream)."""
        req = self._requests[req_id]
        if req.owed:             # tokens, key and blocks as they are
            self._settle("export")
        if req.finished or not req.prefill_done or not req.output_ids:
            raise ValueError(
                "export_request needs an unfinished, fully-prefilled "
                "request with at least one emitted token (prefill "
                "samples the first token from its final logits)")
        if req not in self.scheduler.running:
            raise ValueError(
                "export_request needs a RUNNING request (a preempted "
                "one already carries its snapshot in req.swap)")
        handoff = {
            "prompt_ids": list(req.prompt_ids),
            "output_ids": list(req.output_ids),
            "params": req.params,
            "key": req.key.copy(),
            "kv": self.kv.swap_out(req_id),
        }
        self.scheduler.running.remove(req)
        self._finish_request(req, "migrated")
        req.state = Request.FINISHED
        del self._requests[req_id]
        return handoff

    def adopt_request(self, prompt_ids, sampling_params, output_ids,
                      key, kv) -> int:
        """Admit a mid-flight request exported by another engine's
        `export_request`: the KV snapshot rides the scheduler's
        swap-resume path (restored bit-exactly at admission), decode
        continues from the shipped PRNG key, and — the disaggregation
        point — this engine never runs a prefill program for it: the
        request enters decode-only, so a dedicated decode worker only
        ever dispatches the one fixed-shape ragged(max_num_seqs, 1)
        program."""
        params = sampling_params or SamplingParams()
        prompt = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        out = [int(t) for t in output_ids]
        if not prompt or not out:
            raise ValueError("adopt_request needs a prompt and at least "
                             "one emitted token")
        if len(out) >= params.max_new_tokens:
            raise ValueError("request already finished — ship a result, "
                             "not a handoff")
        if len(prompt) + params.max_new_tokens > self.max_model_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({params.max_new_tokens}) exceeds max_model_len "
                f"({self.max_model_len})")
        req = Request(self._next_id, prompt, params)
        self._next_id += 1
        req.output_ids = out
        req.key = np.array(key, np.uint32).reshape(2)
        if params.deadline_s is not None:
            req.deadline = Deadline(params.deadline_s)
        # the exporter's cache covered positions [0, total_len-1) — the
        # last emitted token is fed (and its K/V written) by the next
        # decode step, exactly as if it had been sampled here
        req.num_computed = req.total_len - 1
        req.swap = kv
        self._begin_trace(req, adopted=True)
        self._requests[req.req_id] = req
        self.scheduler.add(req)
        return req.req_id

    def _begin_trace(self, req, **attrs) -> None:
        """Stamp arrival (TTFT's zero point) and, with tracing on, open
        the request's root span + its queue-wait child."""
        req.arrival_t = time.perf_counter()
        req.arrival_ts = time.time()   # wall clock for the reqlog event
        if mtrace.enabled():
            root = mtrace.start_span(
                "serving/request", rid=req.req_id,
                prompt_len=req.prompt_len,
                max_new_tokens=req.params.max_new_tokens, **attrs)
            req.trace = root
            req.queue_span = mtrace.start_span("serving/queue_wait",
                                               parent=root)
            self._trace_ids[req.req_id] = root.trace_id
            while len(self._trace_ids) > mtrace._MAX_TRACES:
                self._trace_ids.popitem(last=False)

    def _end_trace(self, req, finish: str, keep: bool = False) -> None:
        """Close the request's open spans (idempotent — step() ends
        finished requests, release_request() ends aborted ones).
        ``keep=True`` marks the root for tail sampling's always-keep
        path (an SLO-violating but otherwise normal finish)."""
        if req.queue_span is not None:
            req.queue_span.end(finish=finish)
            req.queue_span = None
        if req.trace is not None:
            if keep:
                req.trace.end(finish=finish,
                              tokens=len(req.output_ids), keep=True)
            else:
                req.trace.end(finish=finish,
                              tokens=len(req.output_ids))
            req.trace = None

    def _finish_request(self, req, reason: str) -> None:
        """The ONE request-finish choke point (idempotent): stamp the
        reason, close spans (marking SLO violators kept for tail
        sampling), count the outcome, and emit the wide reqlog event.
        reasons: "stop" = natural finish, "deadline" = deadline expiry,
        "abort" = released mid-flight, "released" = released while
        still queued (never computed), "migrated" = handed off to
        another replica (drain requeue / failover / disaggregated
        prefill→decode handoff — a success elsewhere, not an error),
        "shed" = best-effort work dropped by SLO-aware admission
        control (ISSUE 19 — deliberate, counted good by the SLO
        error_rate)."""
        if req.finish_reason is not None:
            return
        req.finish_reason = reason
        gen = len(req.output_ids)
        ttft = None
        tpot_avg = None
        if req.first_token_t is not None and req.arrival_t is not None:
            ttft = req.first_token_t - req.arrival_t
        if gen >= 2 and req.first_token_t is not None \
                and req.last_token_t is not None:
            tpot_avg = (req.last_token_t - req.first_token_t) / (gen - 1)
        keep = mslo.enabled() and mslo.violates(
            ttft_s=ttft, tpot_avg_s=tpot_avg,
            queue_wait_s=req.queue_wait_s)
        self._end_trace(req, reason, keep=keep)
        tenant = getattr(req.params, "tenant", None)
        if monitor.enabled():
            self._m_finish.labels(reason=reason).inc()
            if tenant and gen:
                self._m_tenant_tokens.labels(tenant=tenant).inc(gen)
        if mreqlog.enabled():
            mreqlog.emit(mreqlog.event(
                req.req_id,
                trace_id=self._trace_ids.get(req.req_id),
                arrival_ts=req.arrival_ts,
                prompt_tokens=req.prompt_len,
                generated_tokens=gen,
                queue_wait_s=req.queue_wait_s,
                ttft_s=ttft,
                tpot_avg_s=tpot_avg,
                tpot_max_s=req.tpot_max,
                prefill_chunks=req.prefill_chunks,
                prefix_hit_tokens=req.prefix_hit_tokens,
                spec_proposed=req.spec_proposed,
                spec_accepted=req.spec_accepted,
                preemptions=req.num_preemptions,
                peak_kv_blocks=req.peak_kv_blocks,
                finish_reason=reason,
                tenant=tenant,
                priority=getattr(req.params, "priority", None)))

    def request_trace(self, req_id) -> list:
        """The request's finished spans (start-ordered dicts with
        trace/span/parent ids, ts_us/dur_us, attrs) — valid after the
        request is released; [] when it was never traced (PTPU_TRACE off
        at add time) or its trace aged out of the bounded store."""
        tid = self._trace_ids.get(req_id)
        return [] if tid is None else mtrace.get_trace(tid)

    def _init_key(self, params: SamplingParams) -> np.ndarray:
        """The request's sampling key as it rests between steps: the two
        words of `jax.random.PRNGKey(seed)` (or of the global generator's
        next key), read to the host once, here.  A greedy row never
        consumes its key and gets `PRNGKey(0)`'s words, zeros, with no
        device work."""
        from ..core import random as _rng

        if not params.do_sample:
            return np.zeros(2, np.uint32)
        key = (jax.random.PRNGKey(params.seed) if params.seed is not None
               else _rng.next_key())
        return np.array(self._to_host(key), np.uint32)

    def request_output(self, req_id) -> np.ndarray:
        """[prompt + generated] int32 ids (dense generate's row shape),
        the token of a step in flight read back first."""
        req = self._requests[req_id]
        if req.owed:
            self._settle("export")
        return np.asarray(req.prompt_ids + req.output_ids, np.int32)

    def release_request(self, req_id, reason: "str | None" = None) -> None:
        """Drop a request's host state (and abort it if unfinished).
        Callers of the add_request/step API must release requests after
        reading their output — a server that never releases retains every
        prompt/output token list forever.  `generate()` releases its own
        requests.  ``reason`` overrides the finish attribution (the
        deadline sweep passes "deadline"); unfinished releases default
        to "released" while still queued, "abort" mid-flight.  A request
        that rides the step in flight has that step read back first (it
        may finish there: then this is the release of a finished one)."""
        req = self._requests.get(req_id)
        if req is None:
            return
        if req.owed:
            self._settle("release")
        del self._requests[req_id]
        if req.finished:
            self._finish_request(req, "stop")
            return
        if reason is None:
            reason = "released" if req.state == Request.WAITING \
                else "abort"
        self._finish_request(req, reason)
        sched = self.scheduler
        if req in sched.running:
            sched.running.remove(req)
            self.kv.free(req_id)
        elif req in sched.waiting:
            sched.waiting.remove(req)
            if req.req_id in self.kv._tables:   # forked child prefix
                self.kv.free(req_id)
        req.swap = None
        req.state = Request.FINISHED

    def has_unfinished(self) -> bool:
        """Work for `step()`: a request to run, or a step to read back."""
        return self.scheduler.has_work() or self._flight is not None

    def settle(self) -> list:
        """Read back the step in flight, if there is one, dispatching
        nothing behind it; returns the requests that finished there.
        Afterwards every request's tokens, key and blocks are what a
        caller that looks at them directly (`engine._requests`, the
        scheduler's lists) expects; `request_output`, `export_request`,
        `fork_request` and `release_request` do this themselves."""
        return list(self._settle("drain"))

    # -- the loop -----------------------------------------------------------

    def generate(self, prompts, sampling_params=None):
        """Run `prompts` (list of id sequences) to completion; returns a
        list of [prompt + generated] int32 arrays in submission order.
        A request aborted by its `SamplingParams.deadline_s` yields None
        in its slot (deadline abort is a cancel, not a truncation)."""
        if sampling_params is None or isinstance(sampling_params,
                                                 SamplingParams):
            params = [sampling_params] * len(prompts)
        else:
            params = list(sampling_params)
            if len(params) != len(prompts):
                raise ValueError("one SamplingParams per prompt (or one "
                                 "shared instance)")
        ids = [self.add_request(p, sp) for p, sp in zip(prompts, params)]
        try:
            while self.has_unfinished():
                self.step()
            # a deadline-expired request was aborted and released
            # mid-loop: its row comes back as None (partial output is
            # dropped with the request — deadline abort is a cancel, not
            # a truncation)
            return [self.request_output(i) if i in self._requests else None
                    for i in ids]
        finally:
            # also on error (e.g. a too-small pool raising mid-loop):
            # abandoning admitted requests would leak their KV blocks and
            # poison the next generate() call's work loop
            for i in ids:
                self.release_request(i)
            self._settle("drain")     # an error left a step in flight

    def _expire_deadlines(self) -> list:
        """Abort every unfinished request whose deadline has passed, via
        the release_request() path (frees its KV blocks / swap snapshot /
        host state — nothing can leak).  Returns the expired ids."""
        expired = [r.req_id for r in self._requests.values()
                   if r.deadline is not None and not r.finished
                   and r.deadline.expired]
        for rid in expired:
            self.release_request(rid, reason="deadline")
            self._m_expired.inc()
        return expired

    def _shed_best_effort(self) -> list:
        """SLO-aware load shedding (ISSUE 19): when the live fast-window
        burn rate breaches `PTPU_SHED_BURN`, drop every still-WAITING
        best-effort request with reason "shed" — bounded time instead of
        queued to death, via the release_request() path so nothing
        leaks.  Interactive/batch classes are never shed (they defer).
        Returns the shed ids."""
        floor = priority_rank("best-effort")
        cand = [r for r in self._requests.values()
                if r.state == Request.WAITING and not r.finished
                and priority_rank(getattr(r.params, "priority", None))
                >= floor]
        if not cand or not mslo.enabled():
            return []
        burn = worst_fast_burn()
        shed = [r for r in cand
                if should_shed(getattr(r.params, "priority", None),
                               burn=burn)]
        for r in shed:
            tenant = getattr(r.params, "tenant", None)
            self.release_request(r.req_id, reason="shed")
            if monitor.enabled() and tenant:
                self._m_tenant_shed.labels(tenant=tenant).inc()
        return [r.req_id for r in shed]

    def step(self) -> list:
        """One scheduler decision, its programs dispatched, and THEN the
        step dispatched by the call before this one read back: the device
        runs that one while the host schedules and prepares this one
        (module docstring).  Returns the requests that FINISHED in the
        step READ BACK - their last token arrived in this call.  With
        nothing to dispatch (`idle`) the step in flight is read back all
        the same; with nothing in flight the call dispatches and returns.
        The `serving/step` span of a call covers both halves: the dispatch
        of step k+1 (whose riders it is filed under) and the readback,
        emit and retire of step k."""
        t0 = time.perf_counter()
        # deterministic hang injection (PTPU_FAULTS="stall@site=engine.step,
        # secs=..."): the step blocks here, completing no span, so the
        # monitor.watchdog post-mortem path is provable in tests
        faults.maybe_stall(site="engine.step")
        self._retired = []       # every `_finish` inside this call adds
        with mtrace.shared_span("serving/step") as step_span:
            self._step_span = step_span    # `_finish` notes its step there
            try:
                out = self._step_phases(t0, step_span)
            finally:
                self._step_span = None
        if mmem.enabled():
            self._memobs_step(out)
        return self._retired

    def _schedule(self):
        """The scheduler's decision.  It evicts nothing while a row owes a
        token (`StepOwed`): the step in flight is read back - which may
        retire the rows whose blocks were wanted - and it decides again."""
        try:
            try:
                return self.scheduler.schedule()
            except StepOwed:
                self._settle("preempt")
                return self.scheduler.schedule()
        except RuntimeError as e:
            # ISSUE 20 pressure forensics: an admission failure ("KV
            # cache too small") leaves a kv_pressure flight dump naming
            # who actually holds the pool, then propagates untouched
            if "KV cache too small" in str(e):
                self._kv_pressure("admission_failure", error=str(e))
            raise

    def _step_phases(self, t0, step_span):
        """step()'s body, phase by phase (the table in the module
        docstring).  `step_span` is the step's shared span with
        PTPU_TRACE=1 and the null span otherwise."""
        if self._flight is not None and not self.scheduler.has_runnable():
            # every row owes its last token and nobody waits: nothing to
            # decide, only the step in flight to read back
            self._settle("idle")
            return SchedulerOutput(kind="idle")
        with mtrace.phase("engine/schedule"):
            self._expire_deadlines()
            self._shed_best_effort()
            out = self._schedule()
            if out.preempted:
                self._m_preempt.inc(len(out.preempted))
                for r in out.preempted:
                    r.num_preemptions += 1
            if step_span:
                # before any phase span ends: the subtree is filed under
                # the traces linked by then
                riders = ([out.prefill_request] if out.prefill_request
                          else []) + list(out.decode_requests)
                for r in riders:
                    step_span.link(r.trace)
                step_span.attrs.update(phase=out.kind, rows=len(riders))
                if self.states:
                    step_span.attrs.update(state_slots=sum(
                        st.slots_in_use for st in self.states.values()))
        prev = self._flight
        if out.kind == "idle":
            if prev is not None:
                self._settle("idle")
            else:
                self._finish(None, idle_since=t0)
        else:
            self._m_dispatched[prev is not None].inc()
            body = (self._step_prefill if out.kind == "prefill"
                    else self._step_decode)
            # `_flight` is still the step before: its sampler's outputs
            # are what the rows that rode it feed from
            self._flight = body(out, step_span, t0)
            if self._settle_each_step:     # so `prev` is None
                self._settle("spec")
            elif prev is not None:
                self._finish(prev)
            elif monitor.enabled():  # no back half, this call: the gauges
                self._observe_pools()
        return out

    def _settle(self, why: str) -> tuple:
        """Read back the step in flight with nothing dispatched behind it
        (the pipeline runs empty: counted by `why`).  No-op without one."""
        flight, self._flight = self._flight, None
        if flight is None:
            return ()
        self._m_settles.labels(why=why).inc()
        return self._finish(flight)

    def _finish(self, flight, idle_since=None) -> tuple:
        """The back half of a step: `flight`'s results read back and
        emitted (nothing for None), finished requests retired, the clocks.
        Once a program step, so its phases are counted once a step too: a
        call of `step()` that dispatches onto an empty pipeline has none.
        `serving/step_time{phase}` and the rest of the step record
        (`_observe_step`) are observed here and nowhere else, once a program
        step - a settled step, a `prefill`-role replica's and a speculative
        one each come through here once - under the kind of the step READ
        BACK, over the time from the readback before it to its own: what a
        step of that kind costs the longer of the two sides.  An idle step
        (`flight` None, `idle_since` its start) is observed over the call
        and leaves no record."""
        toks = 0
        if flight is not None:
            toks, t_ret, wait = self._read_back(flight)
            dt = t_ret - flight.t_begin
            if self._flight is not None:
                self._flight.t_begin = t_ret
            if self._step_span:
                self._step_span.attrs.update(
                    wait_ms=round(wait * 1e3, 3),
                    host_ms=round((dt - wait) * 1e3, 3))
        with mtrace.phase("engine/retire"):
            if mreqlog.enabled() and self.cache is not None:
                # peak-KV high-water per request: only worth the O(running)
                # walk when someone is collecting the wide events
                for r in self.scheduler.running:
                    blocks = len(self.cache._tables.get(r.req_id, ()))
                    if blocks > r.peak_kv_blocks:
                        r.peak_kv_blocks = blocks
            done = self.scheduler.retire_finished()
            self._retired.extend(done)
            for req in done:
                self._m_done.inc()
                self._finish_request(req, "stop")
            mslo.maybe_tick()   # one module-global read with PTPU_SLO unset
            mtrace.heartbeat()   # step completed — feed the watchdog even
            #                      with tracing off (no span ends to beat)
            if monitor.enabled():
                if flight is not None:
                    self._observe_step(flight.kind, toks, dt, wait,
                                       len(flight.rows))
                elif idle_since is not None:
                    self._observe_step("idle", 0,
                                       time.perf_counter() - idle_since)
                self._observe_pools()
        return done

    def _read_back(self, flight):
        """`flight`'s sampled tokens to the host and into their requests
        -> (tokens to count for the step, when the readback returned, the
        seconds the thread was blocked in it)."""
        t_in = time.perf_counter()
        if flight.arrays is None:      # a prefill chunk that sampled nothing
            return flight.tokens, t_in, 0.0
        with mtrace.phase("engine/readback"):   # blocked on the device
            toks, keys, stats, greedy = self._to_host(flight.arrays)
            if stats is not None:
                for counter, n in zip(self._m_stats[flight.kind], stats):
                    counter.inc(int(n))
        now = time.perf_counter()
        with mtrace.phase("engine/emit"):
            if flight.drafts is not None:
                emitted = self._emit_spec(flight.rows, flight.drafts, toks,
                                          keys, greedy, now)
            else:
                emitted = self._emit(flight.rows, toks, keys, now)
            for sp in flight.spans:
                sp.end()
        return ((flight.tokens if flight.kind == "prefill" else emitted),
                now, now - t_in)

    def _observe_step(self, phase, toks, dt, wait=None, rows=0) -> None:
        """The counters and clocks of one step read back (monitor on).
        `wait` (a program step's; None for an idle one) is what the thread
        was blocked in `_to_host` for it: the device's lead over the host.
        `dt - wait` is the host's side of the step - emit and retire of
        the step before, the pump, schedule, prepare and sample_dispatch
        of the step after, and whatever lies between phases."""
        self._m_step.labels(phase=phase).observe(dt)
        if wait is not None:
            self._m_step_wait.labels(phase=phase).observe(wait)
            host = dt - wait
            if host > _HOST_STALL_S:
                self._m_stalls.inc()
                self._m_stall_s.inc(host)
                monitor.flight.note("host_stall", phase=phase, rows=rows,
                                    host_s=round(host, 6),
                                    wait_s=round(wait, 6))
        # goodput: generated tokens over TOTAL engine wall time —
        # decode_tps reads a single step, this reads the serving
        # story (prefill, scheduling, idle steps all dilute it)
        self._wall_s_total += dt
        if phase == "prefill":
            self._m_pre_toks.inc(toks)
            self._m_pre_tps.set(toks / max(dt, 1e-9))
        elif phase == "decode":
            self._m_dec_toks.inc(toks)
            self._m_dec_tps.set(toks / max(dt, 1e-9))
            self._goodput_toks += toks
        self._m_goodput.set(
            self._goodput_toks / max(self._wall_s_total, 1e-9))

    def _observe_pools(self) -> None:
        """The queue and pool gauges, once a `step()` (monitor on)."""
        sched = self.scheduler
        # queue_depth: admission backlog (never-started requests);
        # waiting: everything not running, preempted included
        self._m_queue.set(sum(1 for r in sched.waiting
                              if r.state == Request.WAITING))
        self._m_running.set(len(sched.running))
        self._m_waiting.set(len(sched.waiting))
        # ISSUE 20: every capacity gauge reads the cache's ONE
        # counts() source — utilization and the admission view
        # (free+parked) can no longer be computed in two places
        for st, (in_use, _) in zip(self.states.values(), self._m_state):
            in_use.set(st.slots_in_use)
        per = [k.counts() for k in self.caches.values()]
        if not per:              # no K/V group: the block gauges stay 0
            return
        c = per[0]
        if len(per) > 1:
            c = {key: sum(p[key] for p in per) for key in c}
        for p, (in_use, _, _) in zip(per, self._m_group):
            in_use.set(p["in_use"])
        self._m_blocks.set(c["in_use"])
        self._m_util.set(c["in_use"] / max(c["total"], 1))
        self._m_kv_free.set(c["free"])
        self._m_kv_parked.set(c["parked"])

    # -- memory microscope (ISSUE 20; PTPU_MEMOBS-gated) --------------------

    def _memobs_step(self, out) -> None:
        """Per-step memory-microscope sampling: one HBM/host timeline
        reading, tenant-labeled capacity attribution, the eviction-
        storm/swap-thrash detector, and the interval-limited /kv pool-
        map publication.  Everything here is host-side dict walking,
        paid only under PTPU_MEMOBS, which no benchmark cell turns on
        (off, the gate is ``tests/test_memobs.py::
        test_memobs_off_gate_counts_nothing``).  It reads K/V blocks:
        a model without a K/V group has nothing for it."""
        cache = self.cache
        if cache is None:
            return
        c = cache.counts()
        # (b) timeline: compiled-program HBM peak (perf capture; None
        # with perf off), live KV-pool bytes, host RSS (TTL-cached)
        peak = None
        for rec in mperf.records():
            pk = rec.peak_bytes
            if pk and (peak is None or pk > peak):
                peak = pk
        mmem.sample(hbm_peak=peak,
                    hbm_in_use=c["in_use"] * cache.bytes_per_block,
                    host_rss=mmem.host_rss_bytes())
        # (d) per-tenant capacity attribution (held now + peak share)
        total = max(c["total"], 1)
        for r in self._requests.values():
            tenant = getattr(r.params, "tenant", None)
            if not tenant:
                continue
            t = cache._tables.get(r.req_id)
            if not t:
                continue
            held = self._tenant_kv_peak.setdefault(tenant, [0, 0.0])
            held[0] += len(t)
        for tenant, held in self._tenant_kv_peak.items():
            blocks, peak_share = held
            self._m_tenant_kv.labels(tenant=tenant).set(blocks)
            share = blocks / total
            if share > peak_share:
                held[1] = share
                self._m_tenant_kv_peak.labels(tenant=tenant).set(share)
            held[0] = 0   # re-summed next step
        # (c) storm / swap-thrash detector: preemptions this step plus
        # parked-block evictions and swap-ins since the last step
        ev = cache.acct.events
        x = (len(out.preempted)
             + (ev["evict"] - self._memobs_prev["evict"])
             + (ev["swap_in"] - self._memobs_prev["swap_in"]))
        self._memobs_prev["evict"] = ev["evict"]
        self._memobs_prev["swap_in"] = ev["swap_in"]
        fire = self._storm.observe(x)
        if fire is not None:
            self._kv_pressure("eviction_storm", **fire)
        # (a) the /kv pool map — rebuilt at most every
        # KV_PUBLISH_INTERVAL_S (the fast path is one monotonic read)
        mmem.maybe_publish_kv(lambda: mmem.build_kv_snapshot(
            cache, list(self._requests.values())))

    def _kv_pressure(self, trigger: str, **info) -> "str | None":
        """Write one rate-limited, replica-tagged ``kv_pressure``
        flight dump naming the ranked pool holders, and refresh the
        published /kv map so the endpoint agrees with the forensics."""
        if not mmem.enabled() or self.cache is None:
            return None
        requests = list(self._requests.values())
        mmem.publish_kv(mmem.build_kv_snapshot(self.cache, requests))
        extra = {"holders": mmem.rank_holders(self.cache, requests),
                 "counts": self.cache.counts()}
        extra.update(info)
        return mmem.reporter().maybe_dump(trigger, extra=extra)

    # -- step bodies --------------------------------------------------------

    def _step_prefill(self, out, step_span, t0) -> _Flight:
        req = out.prefill_request
        start, chunk = out.chunk_start, out.chunk_len
        req.prefill_chunks += 1
        if req.queue_wait_s is None and req.arrival_t is not None:
            # first compute: queue wait over — recorded as a histogram
            # so it is visible with tracing off (ISSUE 16 satellite)
            req.queue_wait_s = time.perf_counter() - req.arrival_t
            self._m_queue_wait.observe(
                req.queue_wait_s,
                trace_id=req.trace.trace_id
                if req.trace is not None else None)
        if req.queue_span is not None:   # first compute: queue wait over
            req.queue_span.end()
            req.queue_span = None
        rows = list(out.decode_requests)      # the running rows it carries
        spans = ()
        if req.trace is not None:
            spans = (mtrace.start_span(
                "serving/prefill", parent=req.trace, chunk_start=start,
                chunk_len=chunk, step=step_span.span_id),)
        spans += tuple(
            mtrace.start_span("serving/decode_step", parent=r.trace,
                              pos=r.total_len - 1, batch=len(rows),
                              step=step_span.span_id)
            for r in rows if r.trace is not None)
        try:
            arrays = self._prefill_body(req, start, chunk, rows)
        except BaseException:
            for sp in spans:
                sp.end()
            raise
        if rows:
            self._m_riders.inc(len(rows))
        return _Flight("prefill", [req] + rows if arrays else [], arrays,
                       chunk, t0, spans=spans)

    def _prefill_body(self, req, start, chunk, rows=()):
        """Dispatch one prefill chunk, carrying the running `rows` where it
        is a whole prompt -> the sampler's device outputs when the chunk
        ends the prompt (`_sample_rows`: the prompt's row first), else
        None."""
        with mtrace.phase("engine/prepare"):
            ids = np.asarray([req.prompt_ids[start:start + chunk]],
                             np.int32)
            whole = start == 0 and chunk == req.prompt_len
            # one slot array a cache group: a whole prompt writes a window
            # group from `tail_start` on, the rest of it is never read
            slots = tuple(
                self._slot_row(k, req.req_id,
                               k.tail_start(chunk) if whole else start,
                               start + chunk)
                for k in self.caches.values())
            srows = tuple(np.asarray([st.slot_of(req.req_id)], np.int32)
                          for st in self.states.values())
            kv = self._kv_flat()
            stats = None
            if whole:
                # whole prompt in one chunk: flash within the chunk, the
                # dense prefill's exact arithmetic
                fn = self._get_prefill_exec(chunk)
                logits, kv_out, stats = self._run(
                    fn, self._param_arrays(), kv, ids, slots,
                    *(self._rider_inputs(rows) if self._mix else (srows,)))
            else:
                tables = tuple(
                    self._table_row(k, req.req_id)[None]
                    for k in self.caches.values())
                fn = self._get_ragged_exec(1, chunk)
                logits, kv_out, stats = self._run(
                    fn, self._param_arrays(), kv, ids,
                    np.asarray([start], np.int32),
                    np.asarray([start + chunk], np.int32), tables, slots,
                    srows)
            self._store_kv(kv_out)
            req.num_computed = start + chunk
            if req.prefix_keys:
                # index the blocks this chunk just filled (full prompt
                # blocks only — their content is final while referenced;
                # whoever adopts them dispatches behind this program)
                self.cache.register_prefix(req.req_id, req.prefix_keys,
                                           req.num_computed)
        if not req.prefill_done:
            return None
        if req.params.max_new_tokens <= 0:
            # dense generate(max_new_tokens=0) emits nothing
            req.state = Request.FINISHED
            return None
        return self._sample_rows([req] + list(rows), logits, stats)

    def _rider_inputs(self, rows) -> tuple:
        """What `prefill(P)` takes of the running rows it carries, at its
        fixed `max_num_seqs - 1` of them: the step in flight's sampled
        tokens (on the device), where each row takes its token from there
        (`_feed_sources`), and `_decode_inputs`' arrays but the state
        slots (a form that mixes has no state group)."""
        b = self.scheduler.max_num_seqs - 1
        toks, pos0, lens, tables, slots, _ = self._decode_inputs(
            rows, [()] * len(rows), b, 1)
        return (self._in_flight_outputs()[0], self._feed_sources(rows, b),
                toks, pos0, lens, tables, slots)

    def _step_decode(self, out, step_span, t0) -> _Flight:
        rows = list(out.decode_requests)
        spans = tuple(
            mtrace.start_span("serving/decode_step", parent=r.trace,
                              pos=r.total_len - 1, batch=len(rows),
                              step=step_span.span_id)
            for r in rows if r.trace is not None)
        try:
            arrays, drafts = self._decode_body(rows)
        except BaseException:
            for sp in spans:
                sp.end()
            raise
        return _Flight("decode", rows, arrays, 0, t0, drafts=drafts,
                       spans=spans)

    def _decode_body(self, rows):
        """Dispatch one decode step -> (the sampler's device outputs, the
        rows' drafts where it was a verify step, else None)."""
        if self.spec_tokens:
            with mtrace.phase("engine/prepare"):   # the host's n-gram scan
                drafts = [self._propose(r) for r in rows]
            if any(drafts):
                return self._decode_body_spec(rows, drafts), drafts
            # zero drafts anywhere this step (cold history, sampling
            # rows, n-gram misses): the plain (bb, 1) program is
            # strictly cheaper — C=1 compute, kernel-eligible on TPU —
            # than a verify launch whose k draft positions are all
            # padding.  Both shapes compile once; steady state stays
            # two launches either way.  (`_emit` gives the scheduler's
            # draft reservation back.)
        return self._decode_body_plain(rows), None

    def _decode_body_plain(self, rows) -> tuple:
        n = len(rows)
        mon = monitor.enabled()
        # ONE fixed shape (max_num_seqs) serves every batch composition:
        # no recompile when the running-request count changes
        bb = self.scheduler.max_num_seqs
        with mtrace.phase("engine/prepare"):
            inputs = self._decode_inputs(rows, [()] * n, bb, 1)
            lens = inputs[2]
            fn = self._get_ragged_exec(bb, 1)
            if self._settle_each_step:
                # nothing is ever in flight: the host holds every token
                logits, kv_out, stats = self._run(
                    fn, self._param_arrays(), self._kv_flat(), *inputs)
            else:
                # the step's one upload: the feed program takes every
                # host array, puts the token of the step in flight where
                # a row rode it, and hands all of them on as device arrays
                inputs = self._run(
                    self._get_feed_exec(bb), self._in_flight_outputs()[0],
                    self._feed_sources(rows, bb), *inputs)
                logits, kv_out, stats = fn(
                    self._param_arrays(), self._kv_flat(), *inputs)
            self._store_kv(kv_out)
            if mon:
                for k, (_, live, held) in zip(self.caches.values(),
                                              self._m_group):
                    live.inc(int(lens.sum()) if k.window is None else
                             int(np.minimum(lens, k.window).sum()))
                    held.inc(k.blocks_in_use)
                for st, (_, held) in zip(self.states.values(),
                                         self._m_state):
                    held.inc(st.slots_in_use)
        arrays = self._sample_rows(rows, logits, stats)
        if mon:
            # padding accounting: bb rows ran, n were real — the
            # serving-goodput blind spot the ragged fixed-shape program
            # introduced.  Decode runs C=1, so rows ARE tokens and the
            # two series carry one value here; they diverge on the
            # speculative path (_decode_body_spec)
            waste = (bb - n) / max(bb, 1)
            self._m_pad_rows.set(waste)
            self._m_pad_toks.set(waste)
        return arrays

    def _in_flight_outputs(self) -> tuple:
        """The sampler's (tokens, keys) of the step in flight, on the
        device; zeros of their shape where no step is, or it sampled
        nothing (no row's source index points there)."""
        flight = self._flight
        if flight is None or flight.arrays is None:
            return self._no_toks, self._no_keys
        return flight.arrays[:2]

    def _feed_sources(self, rows, bb) -> np.ndarray:
        """Where each of `bb` rows takes its input token and sampling key
        from: the row of the step in flight that sampled them (its
        outputs are on the device and nowhere else yet), or -1 for what
        the host holds - a request that owes nothing, and padding."""
        src = np.full((bb,), -1, np.int32)
        for i, req in enumerate(rows):
            if req.owed:
                src[i] = self._flight.row_of[req.req_id]
        return src

    def _table_row(self, k, req_id) -> np.ndarray:
        """A request's block table in cache `k`, padded to the programs'
        table width with `num_blocks` (an out-of-range id: the gathers
        clip it, the masks cover it)."""
        t = k.block_table(req_id)
        if len(t) > self.blocks_per_seq:
            raise BlockAllocatorError(
                f"sequence {req_id} spans {len(t)} blocks > table width "
                f"{self.blocks_per_seq}")
        row = np.full((self.blocks_per_seq,), k.num_blocks, np.int32)
        row[:len(t)] = t
        return row

    def _slot_row(self, k, req_id, lo, hi) -> np.ndarray:
        """Physical slots of positions [lo, hi) of a request in cache `k`,
        as one [1, hi - lo] row: `table[p // bs] * bs + p % bs`."""
        pos = np.arange(lo, hi, dtype=np.int32)
        bs = k.block_size
        table = np.asarray(k.block_table(req_id), np.int32)
        return (table[pos // bs] * bs + pos % bs)[None]

    def _decode_inputs(self, rows, drafts, bb, cw):
        """Inputs of one decode program of fixed shape [bb, cw], as host
        arrays: row i feeds its last token and `drafts[i]`; padding rows
        and unused draft positions keep the dropped-slot sentinel (no
        write, outputs never read).  Tables and slots are one array a
        cache group; a slot is `table[p // bs] * bs + p % bs`, computed
        over the whole batch at once.  The last entry holds one array a
        STATE group: each row's state slot, padding rows the dropped one."""
        n = len(rows)
        toks = np.zeros((bb, cw), np.int32)
        pos0 = np.zeros((bb,), np.int32)
        lens = np.zeros((bb,), np.int32)
        caches = list(self.caches.values())
        tables = [np.full((bb, self.blocks_per_seq), k.num_blocks, np.int32)
                  for k in caches]
        slots = [np.full((bb, cw), k.num_slots, np.int32) for k in caches]
        for i, req in enumerate(rows):
            toks[i, 0] = req.output_ids[-1] if req.output_ids \
                else req.prompt_ids[-1]
            m = len(drafts[i])
            if m:
                toks[i, 1:1 + m] = drafts[i]
            pos0[i] = req.total_len - 1
            lens[i] = req.total_len + m
            for k, tbl in zip(caches, tables):
                tbl[i] = self._table_row(k, req.req_id)
        srows = tuple(
            np.asarray([st.slot_of(r.req_id) for r in rows]
                       + [st.num_slots] * (bb - n), np.int32)
            for st in self.states.values())
        offs = np.arange(cw, dtype=np.int32)
        pos = pos0[:n, None] + offs                 # [n, cw]
        fed = offs[None] < (lens - pos0)[:n, None]  # the positions a row feeds
        row = np.arange(n)[:, None]
        for k, tbl, slt in zip(caches, tables, slots):
            bs = k.block_size
            # an unfed position may lie past the table: its entry is
            # clipped here and its slot is the sentinel
            blk = tbl[row, np.minimum(pos // bs, self.blocks_per_seq - 1)]
            slt[:n] = np.where(fed, blk * bs + pos % bs, k.num_slots)
        return toks, pos0, lens, tuple(tables), tuple(slots), srows

    # -- speculative decoding (ISSUE 15 b) ----------------------------------

    def _propose(self, req) -> list:
        """Draft tokens for one row.  Sampling rows get none — their
        per-request PRNG stream must advance exactly one draw per
        emitted token, the documented scope of the seeded-sampling
        parity guarantee.  The budget clamps so (emitted ≤ drafts+1)
        never overshoots max_new_tokens and no draft position's write
        ever reaches max_model_len."""
        p = req.params
        if p.do_sample:
            return []
        budget = min(self.spec_tokens,
                     p.max_new_tokens - len(req.output_ids) - 1,
                     self.max_model_len - req.total_len)
        if budget <= 0:
            return []
        c = self.config
        return propose_ngram(req.prompt_ids + req.output_ids, budget,
                             ngram_max=c.spec_ngram_max,
                             ngram_min=c.spec_ngram_min,
                             window=c.spec_lookup_window)

    def _decode_body_spec(self, rows, drafts) -> tuple:
        """Speculative decode step: ONE fixed-shape ragged
        (max_num_seqs, k+1) verify program scores the last real token
        plus up to k n-gram drafts per row against the paged pools
        (cache update in-program — write-then-attend puts the drafts'
        K/V in the pool before their own queries run, so in-chunk
        causality is the pool's), the longest greedy-matching draft run
        plus the correction token is accepted, and the block table rolls
        back to the accepted length.  Multiple tokens per step at the
        same TWO program launches as plain decode."""
        n = len(rows)
        mon = monitor.enabled()
        cw = self.spec_tokens + 1      # verify chunk width, fixed
        bb = self.scheduler.max_num_seqs
        with mtrace.phase("engine/prepare"):
            toks, pos0, lens, tables, slots, srows = self._decode_inputs(
                rows, drafts, bb, cw)
            fn = self._get_verify_exec(bb, cw)
            logits0, greedy, kv_out = self._run(
                fn, self._param_arrays(), self._kv_flat(),
                toks, pos0, lens, tables, slots, srows)
            self._store_kv(kv_out)
        # the position-0 logits run through the SAME ("sample", bb)
        # program as plain decode — key threading and sampling rows'
        # streams are bit-identical to spec-off
        arrays = self._sample_rows(rows, logits0)
        if mon:
            real_q = n + sum(len(d) for d in drafts)
            self._m_pad_rows.set((bb - n) / max(bb, 1))
            self._m_pad_toks.set((bb * cw - real_q) / max(bb * cw, 1))
        return arrays[:3] + (greedy,)

    def _emit_spec(self, rows, drafts, toks, new_keys, greedy_h,
                   now) -> int:
        """Per-row acceptance + emission of a verify step read back.
        Greedy rows extend the sampler's token with their longest
        verified draft run: draft j is accepted iff it equals the greedy
        token at position j-1, which validates position j's logits,
        whose greedy token is emitted (the correction/bonus token ends
        the run).  Every table then rolls back to its accepted length
        (rejected-draft blocks return to the pool; finished rows are
        freed by retire_finished right after — truncating first keeps
        the shared-block refcounts exact either way)."""
        emitted = proposed = accepted = 0
        for i, req in enumerate(rows):
            req.owed -= 1
            req.key = new_keys[i]
            out = [int(toks[i])]
            m = len(drafts[i])
            proposed += m
            if not req.params.do_sample:
                g = greedy_h[i]
                # out[0] == g[0]: both argmax the same fp32 logits row
                for j in range(1, m + 1):
                    if int(drafts[i][j - 1]) != int(g[j - 1]):
                        break
                    out.append(int(g[j]))
            row_emitted = 0
            for tok in out:
                req.record_token(tok)
                row_emitted += 1
                self._record_latency(req, now)
                if req.finished:
                    break          # eos inside the accepted run
            emitted += row_emitted
            accepted += row_emitted - 1
            req.spec_proposed += m
            req.spec_accepted += row_emitted - 1
            self.kv.truncate_to(req.req_id, req.total_len)
        self._spec_proposed_total += proposed
        self._spec_accepted_total += accepted
        if monitor.enabled():
            if proposed:
                self._m_spec_prop.inc(proposed)
            if accepted:
                self._m_spec_acc.inc(accepted)
            if self._spec_proposed_total:
                self._m_spec_rate.set(self._spec_accepted_total
                                      / self._spec_proposed_total)
        return emitted

    def _record_latency(self, req, now) -> None:
        """Per-token TTFT/TPOT attribution (the serving-paper
        decomposition); tokens accepted in one spec step share a
        timestamp — their inter-token latency really is ~0.  Each
        observation carries the request's trace_id so PTPU_EXEMPLARS can
        link a bucket to its kept tail-sampled trace."""
        tid = req.trace.trace_id if req.trace is not None else None
        # ISSUE 19: tenant-carrying requests ALSO observe into a
        # tenant-labeled child series; the unlabeled parent observe
        # stays — it is what slo.Objective's latency percentiles read
        tenant = getattr(req.params, "tenant", None)
        if req.first_token_t is None:
            req.first_token_t = now
            if req.arrival_t is not None:
                ttft = now - req.arrival_t
                self._m_ttft.observe(ttft, trace_id=tid)
                if tenant:
                    self._m_ttft.labels(tenant=tenant).observe(ttft)
        else:
            gap = now - req.last_token_t
            self._m_tpot.observe(gap, trace_id=tid)
            if tenant:
                self._m_tpot.labels(tenant=tenant).observe(gap)
            if req.tpot_max is None or gap > req.tpot_max:
                req.tpot_max = gap
        req.last_token_t = now

    def _dispatch_sampler(self, rows, logits):
        """Launch the (\"sample\", B) program over [B, V] fp32 logits (B
        may exceed len(rows) by padding); returns its two device arrays,
        padded to `max_num_seqs` rows.  The model program is still running
        when this returns.  The rows' own parameters decide what the
        program runs (`_sampler_path`: a batch of greedy rows sorts
        nothing); the same arrays count the dispatch into
        `serving/sampler_steps{path}` here.  A row that rode the step in
        flight takes its key from that step's sampler, on the device
        (`_feed_sources`): the host's copy is a step old."""
        with mtrace.phase("engine/sample_dispatch"):
            bb = int(logits.shape[0])
            keys = np.zeros((bb, 2), np.uint32)
            ds = np.zeros((bb,), bool)
            temp = np.ones((bb,), np.float32)
            topk = np.zeros((bb,), np.int32)
            topp = np.ones((bb,), np.float32)
            for i, req in enumerate(rows):
                p = req.params
                keys[i] = req.key
                ds[i] = p.do_sample
                temp[i] = p.temperature
                topk[i] = p.top_k
                topp[i] = p.top_p
            self._m_sampler[_sampler_path(ds, topk, topp)].inc()
            fn = self._get_sample_exec(bb)
            return self._run(
                fn, logits, self._in_flight_outputs()[1],
                self._feed_sources(rows, bb), keys, ds, temp, topk, topp)

    def _sample_rows(self, rows, logits, stats=None):
        """Dispatch the sampler over one token per live row; from here on
        each row owes that token.  -> what `_read_back` reads: the
        sampler's two device arrays, `stats` - what the form's layers
        counted in the program that made `logits` - and no greedy run."""
        toks, new_keys = self._dispatch_sampler(rows, logits)
        for req in rows:
            req.owed += 1
        return toks, new_keys, stats if monitor.enabled() else None, None

    def _emit(self, rows, toks, new_keys, now) -> int:
        """The tokens of a step read back into their requests -> how many
        were emitted.  A row that ended on its `eos_token_id` in the step
        before rode this one unseen: its token is dropped (its blocks
        went back at that step's retire)."""
        emitted = 0
        for i, req in enumerate(rows):
            req.owed -= 1
            if req.finished:
                continue
            req.key = new_keys[i]
            req.record_token(int(toks[i]))
            self._record_latency(req, now)
            emitted += 1
            if self.spec_tokens:
                # no row drafted this step: give the scheduler's (clamped)
                # draft reservation back
                self.kv.truncate_to(req.req_id, req.total_len)
        return emitted

    # -- array plumbing -----------------------------------------------------

    def _run(self, fn, *args):
        """Dispatch a step program.  Its inputs that rest on the host are
        numpy arrays among `args`, and the jitted call's own C++ path
        moves them: one crossing a program, whatever the batch holds (on
        the chip 0.4-0.7 ms a call under one `jax.device_put` of the
        tuple, PERF.md PR 31).  Every host-to-device crossing of a step
        goes through here and counts one
        `serving/device_calls{dir="h2d"}`; the decode program, whose
        inputs the feed program has left on the device, is called
        without."""
        self._m_h2d.inc()
        return fn(*args)

    def _to_host(self, arrays):
        """A pytree of device arrays as host arrays: their copies are
        started together and waited for once.  Every device-to-host
        crossing of the engine goes through here and counts one
        `serving/device_calls{dir="d2h"}`."""
        self._m_d2h.inc()
        return jax.device_get(arrays)

    def _param_arrays(self):
        return self.form.params()

    def _kv_flat(self):
        """Every layer's pools in layer order, a layer's specs in their
        order: (k, v) an attention spec, or (k, v, k_scales, v_scales)
        under int8; (state,) a state spec."""
        return tuple(getattr(c, n)[i] for c, i in self._layer_pools
                     for n in c.pool_names)

    def _store_kv(self, kv_out):
        it = iter(kv_out)
        for c, i in self._layer_pools:
            for n in c.pool_names:
                getattr(c, n)[i] = next(it)

    # -- jitted step programs ----------------------------------------------

    # key-tuple field names per program kind — the engine's jit-cache key
    # IS its compile signature, so the recompile explainer (ISSUE 12)
    # diffs keys instead of arg signatures
    _KEY_FIELDS = {"prefill": ("prompt_len",),
                   "ragged": ("batch", "chunk_len"),
                   "verify": ("batch", "chunk_len"),
                   "feed": ("batch",),
                   "sample": ("batch",)}

    def _count_compile(self, kind: str, key=None) -> None:
        """A step-program cache miss: counted as `serving/compiles{kind}`
        AND into the framework-wide `jit/recompiles{fn}` attribution (the
        engine drives jax.jit directly, bypassing jit.CompiledFunction's
        counter — the flat-across-compositions regression test reads this).

        With `key` (the jit-cache tuple, not yet inserted), the miss is
        additionally EXPLAINED when a same-kind program already exists:
        the first differing key field names the varying axis
        (`jit/recompile_cause{fn,axis}`, e.g. prefill's "prompt_len
        96→128"), and a breadcrumb lands in the
        flight ring so post-mortem dumps explain compile storms.  The
        ragged decode program never varies by batch, so its cause series
        stays empty across compositions — the regression-tested
        invariant."""
        self._m_compiles.labels(kind=kind).inc()
        if not monitor.enabled():
            return
        fname = f"serving:{kind}"
        monitor.counter(
            "jit/recompiles",
            "fresh trace+XLA-compile events per function").labels(
            fn=fname).inc()
        if key is None:
            return
        prior = [k for k in self._jit_cache if k[0] == kind]
        if not prior:
            return   # first program of this kind: a compile, not a RE-compile
        fields = self._KEY_FIELDS.get(kind, ())
        best = max(prior, key=lambda k: sum(
            a == b for a, b in zip(k[1:], key[1:])))
        diffs = [i for i, (a, b) in enumerate(zip(best[1:], key[1:]))
                 if a != b]
        if not diffs:
            return
        i = diffs[0]
        axis = fields[i] if i < len(fields) else f"field{i}"
        detail = f"{axis} {best[1 + i]}→{key[1 + i]}"
        monitor.counter(
            "jit/recompile_cause",
            "recompiles by the signature axis that varied").labels(
            fn=fname, axis=axis).inc()
        monitor.flight.note("jit/recompile", fn=fname, axis=axis,
                            detail=detail)

    @staticmethod
    def _named(fn, name):
        """`fn` under the name its jitted program carries on the
        profiler's `XLA Modules` line (`jit_<name>`)."""
        fn.__name__ = fn.__qualname__ = name
        return fn

    def _model_logits(self, params, h):
        """The form's head over EVERY position (GPT: the dense path's
        ln_f arithmetic and lm_head einsum, so parity tracks the oracle by
        construction).  ALL logits-producing step programs go through the
        form: a change to the oracle tail reaches them all."""
        return self.form.logits(params, h)

    def _model_tail(self, params, h):
        """Last position's fp32 logits — the decode/prefill tail."""
        return self.form.last_logits(params, h).astype(jnp.float32)

    def _run_blocks(self, params, kv_flat, x, pos, attn_builder,
                    valid=None, srows=(), fresh=False):
        """Every layer of the form over `x`; `attn_builder(spec, group
        index, *the spec's pools)` makes the attention an attention
        spec's step calls, `_state_fn` what a state spec's step calls
        over its pool and its group's entry of `srows` (`fresh`: the rows
        start from zeros, a whole-prompt prefill).  A layer is handed one
        such function a spec, in its specs' order.  -> (h, the updated
        pools in layer order, the layers' summed counts or None)."""
        groups, state_groups = list(self._groups), list(self.states)
        h = x
        outs = []
        stats = None
        at = 0
        pools = iter(self._layer_pools)
        for l, specs in enumerate(self._layer_specs):
            fns = []
            for spec in specs:
                width = len(next(pools)[0].pool_names)
                layer_kv = kv_flat[at:at + width]
                at += width
                if isinstance(spec, StateSpec):
                    fns.append(self._state_fn(
                        spec, layer_kv[0],
                        srows[state_groups.index(spec.group)], fresh))
                else:
                    fns.append(attn_builder(spec, groups.index(spec.group),
                                            *layer_kv))
            h, extra, st = self.form.layer(l, params, h, pos, *fns,
                                           valid=valid)
            outs += list(extra)
            if st is not None:
                stats = st if stats is None else stats + st
        return h, tuple(outs), stats

    @staticmethod
    def _state_fn(spec, pool, rows, fresh):
        """What a state layer's step calls (`ServingForm.layer`): the
        rows' states out of the layer's `pool` (zeros when `fresh`), the
        layer's own `step` over them, and the states it returns written
        back to the same slots - the dropped slot for padding rows.  A
        spec that keeps its state `in_place` gets the pool and the slot
        indices themselves and returns the pool: nothing is gathered."""
        if spec.in_place:
            def pool_fn(step):
                y, new = step(pool, rows, fresh)
                return y, (new,)
            return pool_fn

        def state_fn(step):
            prev = (jnp.zeros((rows.shape[0],) + pool.shape[1:], pool.dtype)
                    if fresh else pool[rows])
            y, new = step(prev)
            return y, (pool.at[rows].set(new.astype(pool.dtype)),)
        return state_fn

    @staticmethod
    def _attn_scope(spec):
        return jax.named_scope(spec.scope)

    @staticmethod
    def _sparse_fn(spec, kc, vc, ic, slots, tables=None, lens=None):
        """What a `SparseSpec` layer's step calls: the chunk's K, V and
        index keys stored at `slots`, then its sparse attention - over the
        chunk itself in a whole-prompt prefill (no `tables`), over the
        pools through `tables` in a decode step (one query a row, `lens`
        its context with it).  The ops name their own scopes
        (`dsa/indexer`, `dsa/select`, `attn/sparse`)."""
        from ..ops.sparse_attention import (sparse_decode_attention,
                                            sparse_prefill_attention)

        def sparse_fn(q, k, v, qi, ki, w):
            lanes = ic.shape[-1]
            rows = jnp.pad(ki, ((0, 0), (0, 0), (0, lanes - ki.shape[-1])))
            kc2 = paged_cache_update_arrays(kc, k, slots)
            vc2 = paged_cache_update_arrays(vc, v, slots)
            ic2 = paged_cache_update_arrays(ic, rows, slots)
            if tables is None:
                o = sparse_prefill_attention(q, k, v, qi, ki, w, spec.topk,
                                             spec.scale)
            else:
                if q.shape[1] != 1:
                    raise ValueError(
                        "a sparse layer is decoded one position a row; "
                        "chunked prefill and verify steps are not built")
                o = sparse_decode_attention(
                    q[:, 0], qi[:, 0], w[:, 0], kc2, vc2, ic2, tables, lens,
                    spec.topk, spec.scale)[:, None]
            return o, (kc2, vc2, ic2)
        return sparse_fn

    def _get_prefill_exec(self, p_len):
        key = ("prefill", p_len)
        if key not in self._jit_cache:
            self._count_compile("prefill", key)

            # a window group is written from its tail on (kv_cache.py)
            tails = [k.tail_start(p_len) for k in self.caches.values()]

            def prefill(params, kv_flat, ids, slots, srows=()):
                pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
                x = self.form.embed(params, ids, pos)
                h, kv_out, stats = self._run_blocks(
                    params, kv_flat, x, pos,
                    self._prefill_builder(tails, slots), srows=srows,
                    fresh=True)
                return self._model_tail(params, h), kv_out, stats

            def mixed(params, kv_flat, ids, slots, prev_toks, src, toks,
                      pos0, lens, tables, rslots):
                # the riders' tokens as the feed program selects them
                fed = jnp.where(src >= 0, prev_toks[jnp.maximum(src, 0)],
                                toks[:, 0])
                pos = jnp.concatenate(
                    [jnp.arange(p_len, dtype=jnp.int32), pos0])
                x = self.form.embed(
                    params, jnp.concatenate([ids[0], fed])[None], pos)

                def attend(spec, g, q, k, v, slots, tables, rslots, pos0,
                           lens, *pools):
                    o, written = self._prefill_builder(tails, slots)(
                        spec, g, *pools)(
                        q[:, :p_len], k[:, :p_len], v[:, :p_len])
                    o_r, written = self._ragged_builder(
                        tables, rslots, pos0, lens)(spec, g, *written)(
                        q[0, p_len:, None], k[0, p_len:, None],
                        v[0, p_len:, None])
                    return jnp.concatenate(
                        [o, o_r.reshape((1, -1) + o_r.shape[2:])],
                        axis=1), written

                # one function for every layer of a group, so that a
                # program traces and lowers its attention once a group and
                # not once a layer (set-up time)
                attend = jax.jit(self._named(attend, "attend"),
                                 static_argnums=(0, 1))

                def builder(spec, g, *pools):
                    return lambda q, k, v: attend(
                        spec, g, q, k, v, slots, tables, rslots, pos0, lens,
                        *pools)

                # a padding rider routes nowhere: validity a position
                valid = jnp.concatenate(
                    [jnp.ones((p_len,), bool), lens > 0])[None]
                h, kv_out, stats = self._run_blocks(
                    params, kv_flat, x, pos, builder, valid=valid)
                logits = self.form.logits(params, h[:, p_len - 1:])[0]
                return logits.astype(jnp.float32), kv_out, stats

            self._jit_cache[key] = jax.jit(
                self._named(mixed if self._mix else prefill,
                            f"prefill_{p_len}"),
                donate_argnums=(1,))
        return self._jit_cache[key]

    def _prefill_builder(self, tails, slots):
        """The attention builder of a whole-prompt prefill (`_run_blocks`'
        `attn_builder`): flash within the chunk, its K/V written at
        `slots` (one array a cache group) from the group's `tails` on."""
        from ..ops.pallas_ops import flash_attention_arrays

        def builder(spec, g, kc, vc=None, ksc=None, vsc=None):
            if spec.sparse:
                # a sparse layer's third pool holds index keys
                return self._sparse_fn(spec, kc, vc, ksc, slots[g])
            if spec.latent:
                def latent_fn(rows, whole, stored, pool=kc):
                    # what is stored is the latent; what flash
                    # attends is what the layer expands from it
                    pool2 = latent_cache_update_arrays(
                        pool, rows, slots[g])
                    with self._attn_scope(spec):
                        o = whole(lambda q, k, v: (
                            flash_attention_arrays(
                                q, k, v, is_causal=True,
                                scale=spec.scale)))
                    return o, (pool2,)
                return latent_fn

            def attn_fn(q, k, v, kc=kc, vc=vc, ksc=ksc, vsc=vsc):
                # flash within the chunk reads the fp K/V it just
                # computed — only the STORED cache is quantized
                t0, sl = tails[g], slots[g]
                kw, vw = (k, v) if not t0 else (k[:, t0:], v[:, t0:])
                if ksc is None:
                    kc2 = paged_cache_update_arrays(kc, kw, sl)
                    vc2 = paged_cache_update_arrays(vc, vw, sl)
                    extra = (kc2, vc2)
                else:
                    kc2, ks2 = quantized_cache_update_arrays(
                        kc, ksc, kw, sl)
                    vc2, vs2 = quantized_cache_update_arrays(
                        vc, vsc, vw, sl)
                    extra = (kc2, vc2, ks2, vs2)
                with self._attn_scope(spec):
                    if spec.window is None:
                        o = flash_attention_arrays(
                            q, k, v, is_causal=True)
                    else:
                        o = flash_attention_arrays(
                            q, k, v, is_causal=True,
                            window=spec.window)
                return o, extra
            return attn_fn
        return builder

    def _ragged_blocks(self, c, params, kv_flat, ids, pos0, lens, tables,
                       slots, srows=()):
        """Embeddings, then every block with ONE fused
        `ragged_paged_attention_arrays` call per layer: cache write +
        attention (+ int8 dequant at the block loads) — no separate
        `block_gather/attention/cache_update` triple.  `tables` and
        `slots` hold one array a cache group, `srows` one a state group.
        -> (h, kv_out, stats)."""
        pos = pos0[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        x = self.form.embed(params, ids, pos)
        # a padding row of the fixed-shape batch has no keys
        return self._run_blocks(
            params, kv_flat, x, pos,
            self._ragged_builder(tables, slots, pos0, lens),
            valid=lens > 0, srows=srows)

    def _ragged_builder(self, tables, slots, pos0, lens):
        """The attention builder of a program that reads the pools
        (`_run_blocks`' `attn_builder`): each row's queries at `pos0` on,
        written at `slots` and attending through `tables` (one array a
        cache group each) up to `lens`."""

        def builder(spec, g, kc, vc=None, ksc=None, vsc=None):
            if spec.sparse:
                return self._sparse_fn(spec, kc, vc, ksc, slots[g],
                                       tables[g], lens)
            if spec.latent:
                def latent_fn(rows, whole, stored, pool=kc,
                              tables=tables[g], slots=slots[g]):
                    written = []

                    def attend(q):
                        with self._attn_scope(spec):
                            o, pool2 = ragged_latent_attention_arrays(
                                q, rows, pool, tables, pos0, lens, slots,
                                value_dim=spec.value_dim, scale=spec.scale)
                        written.append(pool2)
                        return o

                    return stored(attend), written
                return latent_fn

            def attn_fn(q, k, v, kc=kc, vc=vc, ksc=ksc, vsc=vsc,
                        tables=tables[g], slots=slots[g]):
                with self._attn_scope(spec):
                    if ksc is None:
                        o, kc2, vc2 = ragged_paged_attention_arrays(
                            q, k, v, kc, vc, tables, pos0, lens, slots,
                            window=spec.window)
                        return o, (kc2, vc2)
                    o, kc2, vc2, ks2, vs2 = ragged_paged_attention_arrays(
                        q, k, v, kc, vc, tables, pos0, lens, slots,
                        k_scales=ksc, v_scales=vsc)
                    return o, (kc2, vc2, ks2, vs2)
            return attn_fn
        return builder

    def _get_ragged_exec(self, b, c):
        """The ISSUE-8 decode program (`_ragged_blocks` + the last
        position's logits).  At (max_num_seqs, 1) this is the single
        compiled program every decode batch composition runs."""
        key = ("ragged", b, c)
        if key not in self._jit_cache:
            self._count_compile("ragged", key)

            def ragged(params, kv_flat, *inputs):
                h, kv_out, stats = self._ragged_blocks(c, params, kv_flat,
                                                       *inputs)
                return self._model_tail(params, h), kv_out, stats

            self._jit_cache[key] = jax.jit(self._named(
                ragged, "ragged_decode" if c == 1 else f"ragged_prefill_{c}"),
                donate_argnums=(1,))
        return self._jit_cache[key]

    def _get_verify_exec(self, b, c):
        """The ISSUE-15 multi-token scoring program: `_ragged_blocks` at
        [b, c], returning EVERY position's greedy argmax plus the
        position-0 fp32 logits (the sampler's input).  ONE fixed shape
        (max_num_seqs, spec_tokens+1) serves every batch composition and
        every draft hit/miss mix — padded draft positions carry dropped
        slots and their outputs are never read."""
        key = ("verify", b, c)
        if key not in self._jit_cache:
            self._count_compile("verify", key)

            def verify(params, kv_flat, *inputs):
                h, kv_out, _ = self._ragged_blocks(c, params, kv_flat,
                                                   *inputs)
                logits = self._model_logits(params, h).astype(jnp.float32)
                greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return logits[:, 0], greedy, kv_out

            self._jit_cache[key] = jax.jit(
                self._named(verify, "spec_verify"), donate_argnums=(1,))
        return self._jit_cache[key]

    def _get_feed_exec(self, b):
        """The program that puts a decode step's inputs on the device:
        `(prev_toks, src, toks, *rest)`, all but the first from the host,
        -> `(toks, *rest)` as device arrays, where row i's token is
        `prev_toks[src[i]]` - what the step in flight sampled for it, which
        the host has not seen - wherever `src[i] >= 0`.  A program of its
        own so that the model programs stay what they are; ONE shape
        whatever the mix of rows fed from the device and from the host."""
        key = ("feed", b)
        if key not in self._jit_cache:
            self._count_compile("feed", key)

            def feed(prev_toks, src, toks, *rest):
                fed = jnp.where(src >= 0, prev_toks[jnp.maximum(src, 0)],
                                toks[:, 0])
                return (fed[:, None],) + rest

            self._jit_cache[key] = jax.jit(self._named(feed, "feed"))
        return self._jit_cache[key]

    def _get_sample_exec(self, b):
        """`_sample_program` over `b` rows, between what this engine adds
        around it: a row's key comes from `prev_keys[src]`, the keys the
        step in flight left on the device, wherever `src >= 0` (else from
        `keys`, the host's), and both results are padded to `max_num_seqs`
        rows, so that the next step's programs take a prefill's one-row
        outputs and a decode step's in one shape."""
        key = ("sample", b)
        if key not in self._jit_cache:
            self._count_compile("sample", key)
            pad = self.scheduler.max_num_seqs - b

            def sample(logits, prev_keys, src, keys, *params):
                keys = jnp.where((src >= 0)[:, None],
                                 prev_keys[jnp.maximum(src, 0)], keys)
                toks, new_keys = _sample_program(logits, keys, *params)
                return (jnp.pad(toks, (0, pad)),
                        jnp.pad(new_keys, ((0, pad), (0, 0))))

            self._jit_cache[key] = jax.jit(self._named(sample, "sample"))
        return self._jit_cache[key]
