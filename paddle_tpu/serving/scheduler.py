"""Continuous-batching scheduler (the MPK lesson from PAPERS.md applied to
serving: scheduling lives OUTSIDE the compiled step, so one jitted decode
program serves an ever-changing request mix).

Per engine step the scheduler picks ONE of:

- a **prefill** for the head of the waiting queue (prefill-priority, the
  classic continuous-batching policy: new requests join the decode batch
  at the earliest step), chunked to the token budget
  (`max_num_batched_tokens`), admitted only when the KV pool can hold the
  chunk;
- a **decode** over every RUNNING request, after reserving each row's next
  slot — reservation failures trigger **preemption by eviction**: the
  youngest running request is swapped out (host snapshot, blocks freed,
  re-queued at the FRONT of the waiting queue so arrival order is
  preserved) until the rest fit.  Evicting the youngest minimizes wasted
  work — the oldest requests are closest to finishing.

With `mix_decode` (the engine's choice, from its model's layers) a prefill
of a WHOLE prompt also carries the running rows: each whose next slot can
be reserved without an eviction decodes one token in the same step
(`SchedulerOutput.decode_requests` of a "prefill"), so the rows do not
stall while the prompt runs.  A chunk, a prefix hit's tail, a forked
child's continuation and a swap-in carry none, and nothing rides where
`spec_tokens > 0`.

Multi-tenant policy (ISSUE 19): requests carry a `tenant` and a
`priority` class.  Admission candidates are ordered by (priority class,
weighted tenant service, arrival) — a deficit-style fair share where every
prefill chunk and decode slot charges `tokens / weight` against the
tenant's running total, so a burst tenant's normalized service grows and
its queued requests yield the admission head to under-served tenants.
Preemption evicts lowest-priority-youngest first.  With default params
(no tenant, one priority) every ordering degenerates to the original
FIFO/youngest policy bit-for-bit.

The engine keeps one step in flight (ISSUE 35): a request may OWE a token
that a dispatched step has sampled and the host has not read (`Request.
owed`).  The scheduler counts it: an owing request is one position longer
(`total_len`), is not decoded again when the owed token is its last by
count, and nothing is evicted while any row owes - `_evict` raises
`StepOwed`, the engine reads the step back and asks again.

The scheduler owns request state machines and the block accounting calls;
it never touches device math — that is `engine.LLMEngine`'s half.
"""
from __future__ import annotations

import dataclasses
import os
from collections import deque
from typing import Optional

__all__ = ["SamplingParams", "Request", "Scheduler", "SchedulerOutput",
           "StepOwed", "PRIORITIES", "priority_rank", "tenant_weights",
           "should_shed", "worst_fast_burn"]

# Priority classes, best first.  Admission prefers lower rank; eviction
# victimizes higher rank.  Unknown strings rank with "best-effort" so a
# typo'd class degrades service instead of jumping the queue.
PRIORITIES = ("interactive", "batch", "best-effort")
_PRIORITY_RANK = {name: i for i, name in enumerate(PRIORITIES)}

# Shed threshold: best-effort traffic is shed once the worst fast-window
# SLO burn rate reaches this multiple of budget burn (2.0 = burning error
# budget at twice the sustainable rate).
_SHED_DEFAULT_BURN = 2.0


def priority_rank(priority) -> int:
    """Rank of a priority class — lower is better; unknown ranks worst."""
    return _PRIORITY_RANK.get(priority, len(PRIORITIES) - 1)


def tenant_weights(spec: Optional[str] = None) -> dict:
    """Parse a ``name:weight,name:weight`` spec (default: the
    ``PTPU_TENANT_WEIGHTS`` env var).  Unlisted tenants weigh 1.0; zero,
    negative, or malformed weights are dropped rather than raising — a
    bad env var must not take the serving loop down."""
    if spec is None:
        spec = os.environ.get("PTPU_TENANT_WEIGHTS", "")
    out: dict = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, raw = part.partition(":")
        try:
            weight = float(raw) if raw else 1.0
        except ValueError:
            continue
        if name.strip() and weight > 0:
            out[name.strip()] = weight
    return out


def worst_fast_burn(report=None) -> float:
    """Worst fast-window burn rate across all SLO objectives, 0.0 when
    the SLO engine is off (shedding never triggers without live SLOs)."""
    if report is None:
        from ..monitor import slo as mslo
        report = mslo.report()
    if not report or not report.get("enabled"):
        return 0.0
    worst = 0.0
    for obj in report.get("objectives", ()):
        rate = (obj.get("burn_rate") or {}).get("fast")
        if rate is not None:
            worst = max(worst, float(rate))
    return worst


def should_shed(priority, burn: Optional[float] = None) -> bool:
    """SLO-aware admission control: shed `priority`-class work right now?

    Only "best-effort" is ever shed — interactive and batch classes defer
    (stay queued) rather than drop.  The decision input is the worst
    fast-window burn rate from the live `monitor.slo` engine (injectable
    via `burn` for tests), against the `PTPU_SHED_BURN` threshold."""
    if priority_rank(priority) < priority_rank("best-effort"):
        return False
    if burn is None:
        burn = worst_fast_burn()
    try:
        threshold = float(os.environ.get("PTPU_SHED_BURN",
                                         _SHED_DEFAULT_BURN))
    except ValueError:
        threshold = _SHED_DEFAULT_BURN
    return burn >= threshold


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling controls — field-for-field the knobs of
    `GPTForCausalLM.generate` (the parity oracle)."""

    max_new_tokens: int = 16
    do_sample: bool = False
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    eos_token_id: Optional[int] = None
    seed: Optional[int] = None
    # wall-clock budget from admission; an expired request is aborted at
    # the next engine step via release_request() (resilience.Deadline —
    # None = no deadline).  Not a sampling knob, so absent from the dense
    # generate() oracle surface.
    deadline_s: Optional[float] = None
    # -- multi-tenant scheduling (ISSUE 19) --------------------------------
    # Tenant for weighted fair-share accounting (None = the shared default
    # pool) and priority class ("interactive" | "batch" | "best-effort").
    # Router-wire-safe: params_from_wire drops fields older peers don't
    # declare, so mixed-version fleets fall back to default-pool FIFO.
    tenant: Optional[str] = None
    priority: str = "interactive"


class Request:
    """One in-flight generation: prompt, sampling state, and progress."""

    WAITING, RUNNING, PREEMPTED, FINISHED = range(4)

    def __init__(self, req_id, prompt_ids, params: SamplingParams):
        self.req_id = req_id
        self.prompt_ids = list(int(t) for t in prompt_ids)
        self.params = params
        self.state = Request.WAITING
        self.output_ids: list = []         # generated tokens (incl. eos)
        self.owed = 0                      # tokens sampled by a dispatched
        #                                    step and not read back (engine)
        self.num_computed = 0              # prompt tokens prefilled so far
        self.key = None                    # per-request PRNG key: a host
        #                                    numpy uint32[2] (engine)
        self.swap = None                   # host KV snapshot while evicted
        self.prefix_keys = None            # chained block keys (engine;
        #                                    set only with prefix caching)
        self.prefix_hit_tokens = 0         # prompt tokens adopted cached
        self.arrival = None                # admission tiebreak (set by add)
        self.deadline = None               # resilience.Deadline (engine)
        # -- observability (engine-owned; monitor.trace v2) ----------------
        self.trace = None                  # root Span, or None (trace off)
        self.queue_span = None             # open queue-wait child Span
        self.arrival_t = None              # perf_counter at add_request
        self.first_token_t = None          # perf_counter of token 1 (TTFT)
        self.last_token_t = None           # perf_counter of latest token
        # -- request-plane wide event (engine-owned; ISSUE 16) -------------
        self.arrival_ts = None             # wall clock at add_request
        self.queue_wait_s = None           # arrival to first compute
        self.tpot_max = None               # worst inter-token gap, seconds
        self.prefill_chunks = 0            # prefill passes this prompt took
        self.num_preemptions = 0           # times evicted mid-flight
        self.peak_kv_blocks = 0            # high-water KV blocks held
        self.spec_proposed = 0             # draft tokens proposed (this req)
        self.spec_accepted = 0             # draft tokens accepted (this req)
        self.finish_reason = None          # stop|abort|deadline|released|
        #                                    shed, set exactly once at finish

    # -- derived ------------------------------------------------------------

    @property
    def prompt_len(self) -> int:
        return len(self.prompt_ids)

    @property
    def total_len(self) -> int:
        """Prompt + generated tokens, the owed one counted: the next
        decode step writes position `total_len - 1`."""
        return self.prompt_len + len(self.output_ids) + self.owed

    @property
    def prefill_done(self) -> bool:
        return self.num_computed >= self.prompt_len

    @property
    def finished(self) -> bool:
        return self.state == Request.FINISHED

    @property
    def owes_last(self) -> bool:
        """The token it owes is its last by count: no step is left for it
        to ride (an `eos_token_id` is found out only at readback)."""
        return bool(self.owed) and (len(self.output_ids) + self.owed
                                    >= self.params.max_new_tokens)

    def record_token(self, tok: int) -> None:
        self.output_ids.append(int(tok))
        p = self.params
        if len(self.output_ids) >= p.max_new_tokens or (
                p.eos_token_id is not None and int(tok) == p.eos_token_id):
            self.state = Request.FINISHED

    def __repr__(self):
        names = {0: "WAITING", 1: "RUNNING", 2: "PREEMPTED", 3: "FINISHED"}
        return (f"Request({self.req_id}, state={names[self.state]}, "
                f"prompt={self.prompt_len}, out={len(self.output_ids)})")


class StepOwed(Exception):
    """Raised by `schedule()` before it evicts anything while a running
    request owes a token: a swap snapshot carries a request's tokens and
    key as the host has them.  The engine reads the step in flight back
    and calls `schedule()` again (what the first call did until then -
    `grow_to`, a swap-resume - stands)."""


@dataclasses.dataclass
class SchedulerOutput:
    """What the engine must run this step."""

    kind: str                      # "prefill" | "decode" | "idle"
    prefill_request: Optional[Request] = None
    chunk_start: int = 0           # prefill: first prompt position of chunk
    chunk_len: int = 0
    decode_requests: tuple = ()    # decode: rows of the batch; prefill:
    #                                the running rows it carries
    preempted: tuple = ()          # requests evicted while scheduling


class Scheduler:
    def __init__(self, cache, max_num_seqs=8, max_num_batched_tokens=2048,
                 spec_tokens=0, max_model_len=None, weights=None,
                 mix_decode=False):
        self.cache = cache
        self.max_num_seqs = int(max_num_seqs)
        self.max_num_batched_tokens = int(max_num_batched_tokens)
        # deficit-style weighted fair share (ISSUE 19): normalized service
        # per tenant (tokens / weight), charged at prefill-chunk emission
        # and per decode slot.  `weights` overrides the env knob for
        # tests; None = PTPU_TENANT_WEIGHTS.
        self.tenant_weights = (dict(weights) if weights is not None
                               else tenant_weights())
        self.tenant_served: dict = {}
        # speculative decoding (ISSUE 15): a decode step may write up to
        # `spec_tokens` draft positions past each row's last token, so
        # the decode branch reserves blocks for that extent up front (the
        # engine rolls the table back to the ACCEPTED length after the
        # step).  Clamped per row so no write position ever reaches
        # max_model_len.
        self.spec_tokens = max(0, int(spec_tokens))
        # a whole prompt's prefill carries the running rows (module
        # docstring); a verify step's drafts have no such program
        self.mix_decode = bool(mix_decode) and not self.spec_tokens
        self.max_model_len = (None if max_model_len is None
                              else int(max_model_len))
        self.waiting: deque = deque()
        self.running: list = []
        self._arrival = 0
        # ISSUE 20 memory microscope: plain-int pressure ledger the
        # engine's eviction-storm detector reads per-step deltas of
        # (always counted — two int adds per rare event, no gate)
        self.num_evictions = 0
        self.num_swap_ins = 0

    def _decode_reserve_len(self, req) -> int:
        """Token coverage the decode step needs for `req`: total_len (the
        non-spec write of position total_len-1) plus the row's REAL draft
        budget — the same clamp the engine's proposer applies, so rows
        that can never carry drafts (sampling rows, rows within one token
        of max_new_tokens or max_model_len) reserve nothing extra and
        can't evict a neighbour for blocks nobody will write."""
        extra = self.spec_tokens
        if extra:
            p = req.params
            if p.do_sample:
                extra = 0
            else:
                extra = min(extra,
                            p.max_new_tokens - len(req.output_ids) - 1)
                if self.max_model_len is not None:
                    extra = min(extra, self.max_model_len - req.total_len)
        return req.total_len + max(0, extra)

    # -- request lifecycle --------------------------------------------------

    def add(self, req: Request) -> None:
        req.arrival = self._arrival
        self._arrival += 1
        self.waiting.append(req)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    def has_runnable(self) -> bool:
        """Could `schedule()` find a step to run: someone waits, or a
        running request has a step left to ride."""
        return bool(self.waiting) or any(
            not (r.finished or r.owes_last) for r in self.running)

    # -- multi-tenant fair share (ISSUE 19) --------------------------------

    @staticmethod
    def _tenant_of(req) -> str:
        return getattr(req.params, "tenant", None) or "default"

    def _charge(self, req, tokens: int) -> None:
        """Charge `tokens` of service against the request's tenant,
        normalized by its configured weight — a weight-3 tenant pays a
        third of the fair-share price per token, so it sustains 3x the
        throughput before yielding the admission head."""
        if tokens <= 0:
            return
        tenant = self._tenant_of(req)
        weight = self.tenant_weights.get(tenant, 1.0)
        self.tenant_served[tenant] = (self._served_of(tenant)
                                      + tokens / weight)

    def _served_of(self, tenant) -> float:
        got = self.tenant_served.get(tenant)
        if got is None:
            # a never-seen tenant starts at the current minimum, not 0 —
            # starting from zero would let a late joiner monopolize
            # admission until it "caught up" with incumbents' history
            got = min(self.tenant_served.values(), default=0.0)
        return got

    def _admission_key(self, req):
        """Candidate ordering for admission: priority class first, then
        least normalized tenant service, then arrival.  With default
        params every key collapses to (0, served, arrival) with `served`
        shared by all — exact FIFO."""
        return (priority_rank(getattr(req.params, "priority", None)),
                self._served_of(self._tenant_of(req)),
                req.arrival)

    # -- the policy ---------------------------------------------------------

    def schedule(self) -> SchedulerOutput:
        preempted = []
        # 1) continue a partially-prefilled running request (chunked
        #    prefill spans several steps; it must finish before decoding)
        part = next((r for r in self.running if not r.prefill_done), None)
        if part is not None:
            if self._ensure_blocks(
                    part, min(part.prompt_len,
                              part.num_computed
                              + self.max_num_batched_tokens),
                    preempted, protect=part):
                return self._emit_prefill(part, preempted)
            return SchedulerOutput(kind="idle", preempted=tuple(preempted))
        # 2) admit / resume from the waiting queue (no eviction on behalf
        #    of admission — preemption exists to keep RUNNING work
        #    progressing, not to thrash between queued requests).  The
        #    admission head is the best (priority, fair-share, arrival)
        #    candidate — plain FIFO when every request carries defaults —
        #    and the deque itself is never reordered; when the head is
        #    blocked and NOTHING is running, any other schedulable entry
        #    (e.g. a forked child already holding shared blocks whose
        #    completion will free them) is tried before declaring the
        #    pool too small.
        if self.waiting and len(self.running) < self.max_num_seqs:
            order = sorted(self.waiting, key=self._admission_key)
            got = self._admit_or_resume(order[0], preempted)
            if isinstance(got, SchedulerOutput):
                return self._with_riders(got)
            if got is None and not self.running:
                for req in order[1:]:
                    got = self._admit_or_resume(req, preempted)
                    if isinstance(got, SchedulerOutput):
                        return got
                    if got:
                        break
                else:
                    head = self.waiting[0]
                    if head.swap is not None:
                        raise RuntimeError(
                            "KV cache too small: an evicted request can "
                            "never be restored "
                            f"(free={self.cache.num_free_blocks} blocks, "
                            f"needs {self.cache.swap_blocks(head.swap)})")
                    raise RuntimeError(
                        "KV cache too small: cannot hold a single request "
                        f"(free={self.cache.num_free_blocks} blocks, "
                        "prompt chunk needs "
                        f"{self.cache.blocks_needed(min(head.prompt_len, self.max_num_batched_tokens))})")
            # got is True: a swap-resume landed in running with no step to
            # emit (mid-prefill resumes continue via branch 1 next call)
        # 3) decode every running request, reserving one slot per row
        if self.running:
            rows = []
            for req in list(self.running):   # oldest first
                if req.state != Request.RUNNING or not req.prefill_done:
                    continue                 # evicted mid-loop / mid-prefill
                if req.owes_last:
                    continue
                # this step writes position total_len - 1 (the last
                # sampled token's K/V) — coverage of total_len tokens is
                # exactly enough (one more would take a block a step
                # early) — plus the speculative draft extent when spec
                # decoding is on (rolled back to the accepted length by
                # the engine after the step)
                reserve = self._decode_reserve_len(req)
                if not self._ensure_blocks(req, reserve, preempted,
                                           protect=req):
                    continue                 # req itself was evicted
                self.cache.grow_to(req.req_id, reserve)
                rows.append(req)
            # a LATER row's reservation may have evicted an EARLIER row
            # that already made it into the batch — a preempted row's
            # table is gone, so it must not reach the engine
            rows = [r for r in rows if r.state == Request.RUNNING]
            if rows:
                for r in rows:       # one decode slot = one token served
                    self._charge(r, 1)
                return SchedulerOutput(kind="decode",
                                       decode_requests=tuple(rows),
                                       preempted=tuple(preempted))
        return SchedulerOutput(kind="idle", preempted=tuple(preempted))

    def _admit_or_resume(self, req, preempted):
        """Try to start `req`: returns a SchedulerOutput to emit (a
        prefill step), True when a swap-resume landed in `running` with
        no step to emit, or None when it cannot start right now."""
        if req.swap is not None:
            if not self._can_swap_in(req):
                return None
            self.waiting.remove(req)
            self.cache.swap_in(req.req_id, req.swap)
            req.swap = None
            req.state = Request.RUNNING
            self.running.append(req)
            self.num_swap_ins += 1
            return True
        start = req.num_computed    # >0 only for forked children, which
        #                             already hold (shared) prefix blocks.
        forked = req.req_id in self.cache._tables
        # Automatic prefix caching (ISSUE 15): a fresh request first
        # matches its chained block keys against the prefix index and
        # adopts the longest cached run by refcount bump — capped below
        # the full prompt (the last prompt token must be recomputed for
        # its logits) and block-aligned (only full, never-rewritten
        # blocks are shared).  Adoption happens ONLY when the remaining
        # chunk also fits, so a failed admission holds no blocks.
        hit_blocks = 0
        if (not forked and start == 0 and req.prefix_keys
                and not req.prefix_hit_tokens):
            hit_blocks = self.cache.match_prefix(
                req.prefix_keys,
                max_blocks=(req.prompt_len - 1) // self.cache.block_size)
        # The prefill-chunking token budget counts only UNCACHED tokens:
        # a prefix-hit request's chunk starts at the first uncached
        # token, so a hot request admits its real remaining work instead
        # of being under-batched by its (already-paid) cached prefix.
        # (a model with state groups alone has no block: nothing to hit)
        hit_tokens = hit_blocks * self.cache.block_size if hit_blocks else 0
        chunk = min(req.prompt_len - start - hit_tokens,
                    self.max_num_batched_tokens)
        target = start + hit_tokens + chunk
        if hit_blocks:
            need = self.cache.blocks_needed(target) - hit_blocks
            fits = need <= self.cache.adoptable_free_blocks(
                req.prefix_keys, hit_blocks)
        elif forked:
            fits = self.cache.can_grow_to(req.req_id, target)
        else:
            # a whole prompt in one chunk reads its keys from the chunk:
            # a window group then holds the prompt's tail alone
            whole = start == 0 and target == req.prompt_len
            fits = self.cache.can_allocate(target, tail_only=whole)
        if not fits:
            return None
        self.waiting.remove(req)
        if hit_blocks:
            req.prefix_hit_tokens = self.cache.adopt_prefix(
                req.req_id, req.prefix_keys, hit_blocks)
            req.num_computed = req.prefix_hit_tokens
            start = req.num_computed
            self.cache.grow_to(req.req_id, target)
        elif forked:
            self.cache.grow_to(req.req_id, target)
        else:
            self.cache.allocate(req.req_id, target, tail_only=whole)
        req.state = Request.RUNNING
        self.running.append(req)
        self._charge(req, chunk)
        return SchedulerOutput(kind="prefill", prefill_request=req,
                               chunk_start=start, chunk_len=chunk,
                               preempted=tuple(preempted))

    def _with_riders(self, out) -> SchedulerOutput:
        """`out`, a prefill just admitted, with the running rows it carries
        where it is a whole prompt and `mix_decode` is on: every row that
        `schedule()`'s decode branch would take and whose next slot needs
        no eviction (a row that does waits for the next decode step, where
        it may evict)."""
        req = out.prefill_request
        if (not self.mix_decode or out.chunk_start
                or out.chunk_len != req.prompt_len
                or req.params.max_new_tokens <= 0):
            return out
        rows = []
        for r in self.running:           # oldest first
            if r is req or not r.prefill_done or r.owes_last:
                continue
            reserve = self._decode_reserve_len(r)
            if not self.cache.can_grow_to(r.req_id, reserve):
                continue
            self.cache.grow_to(r.req_id, reserve)
            self._charge(r, 1)
            rows.append(r)
        out.decode_requests = tuple(rows)
        return out

    def _emit_prefill(self, req, preempted) -> SchedulerOutput:
        start = req.num_computed
        chunk = min(req.prompt_len - start, self.max_num_batched_tokens)
        self.cache.grow_to(req.req_id, start + chunk)
        self._charge(req, chunk)
        return SchedulerOutput(
            kind="prefill", prefill_request=req, chunk_start=start,
            chunk_len=chunk, preempted=tuple(preempted))

    # -- eviction -----------------------------------------------------------

    def _can_swap_in(self, req) -> bool:
        return self.cache.can_swap_in(req.swap)

    def _ensure_blocks(self, req, target_len, preempted, protect=None) -> bool:
        """Make the pool able to cover `target_len` for `req`, evicting
        youngest-first as needed.  Returns False if `req` itself had to be
        evicted (nothing younger was left to take)."""
        while not self.cache.can_grow_to(req.req_id, target_len):
            victim = self._pick_victim(exclude=protect)
            if victim is None:
                # self-eviction only helps when someone ELSE still holds
                # blocks (e.g. forked children in the waiting queue); a
                # request that cannot fit in the EMPTY pool would evict
                # itself, swap back in, and livelock forever — raise
                if not self.cache.fits_empty(req.req_id, target_len):
                    raise RuntimeError(
                        "KV cache too small: request needs "
                        f"{self.cache.blocks_needed(target_len)} blocks "
                        f"for {target_len} tokens but the pool holds only "
                        f"{self.cache.num_blocks}; raise "
                        "EngineConfig.num_blocks or lower max_new_tokens")
                if protect is not None and protect in self.running:
                    self._evict(protect, preempted)
                    return False
                raise RuntimeError(
                    "KV cache too small: cannot hold a single request "
                    f"(free={self.cache.num_free_blocks} blocks, request "
                    f"needs {self.cache.blocks_needed(target_len)})")
            self._evict(victim, preempted)
        return True

    def _pick_victim(self, exclude=None):
        # lowest priority class first, then youngest ARRIVAL — not list
        # position: swap-ins re-append resumed (older) requests at the
        # tail, so list order is not age order.  One priority class in
        # play reduces this to the original youngest-arrival pick.
        victims = [r for r in self.running if r is not exclude]
        if not victims:
            return None
        return max(victims, key=lambda r: (
            priority_rank(getattr(r.params, "priority", None)), r.arrival))

    def _evict(self, req, preempted) -> None:
        if any(r.owed for r in self.running):
            raise StepOwed
        req.swap = self.cache.swap_out(req.req_id)
        req.state = Request.PREEMPTED
        self.running.remove(req)
        self.waiting.appendleft(req)             # keeps arrival order
        preempted.append(req)
        self.num_evictions += 1

    # -- completion ---------------------------------------------------------

    def retire_finished(self) -> tuple:
        done = tuple(r for r in self.running if r.finished)
        for req in done:
            self.cache.free(req.req_id)
            self.running.remove(req)
        return done
