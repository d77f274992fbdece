"""Memory microscope — *who holds the memory* (ISSUE 20, monitor v8).

The monitor stack can see programs (perf), requests (reqlog/trace), and
the fleet (fleet), but an admission failure or preemption storm leaves
no forensic record of which requests, tenants, or parked prefix blocks
were squatting on the KV pool, and ``perf/hbm_headroom`` is a
per-program point reading with no history.  This module is the
memory-side instrument plane the ZeRO-sharding and KV-tiering arcs
(ROADMAP items 3/4) will be gated and debugged with.  Four wings:

- **KV block-lifecycle accounting** (:class:`KVAccounting`, owned by
  ``serving.kv_cache.BlockKVCache``): one counter family
  ``serving/kv_blocks{event}`` over every pool transition —
  alloc / free / fork / cow / park / adopt / evict / swap_out /
  swap_in, per block — plus a ``serving/kv_parked_residency_age``
  histogram of how long a parked prefix block stayed adoptable before
  reclaim (the live twin of ``serving/prefix_evictions``: item 4's
  "hot system prompt should survive pressure" invariant needs age
  data, not just an eviction count).  :func:`fragmentation` analyses
  the free list's contiguity (runs vs. contiguous capacity).
- **HBM/host timeline**: a bounded ring of sampled
  ``(monotonic-ts, hbm_peak, hbm_in_use, host_rss)`` readings
  (:func:`sample`) fed from the existing perf capture and the
  ``/healthz`` rss path, mirrored into ``memory/...`` gauges and
  served at ``GET /memory/timeline`` — headroom regressions become a
  trendline instead of a point reading.
- **Pressure forensics**: :class:`StormDetector` (EWMA mean/variance
  over per-step eviction+swap events, the ``LossSpikeDetector`` shape)
  and :class:`PressureReporter`, which writes a replica-tagged
  ``kv_pressure`` flight dump naming ranked holders
  (:func:`rank_holders`: requests by blocks held x age, parked prefix
  chains by residency, tenants by share) — rate-limited so a storm
  produces ONE dump, not thousands.
- **Pool-map publication**: the engine's step loop builds a
  :func:`build_kv_snapshot` document and publishes it here
  (:func:`maybe_publish_kv`, interval-limited); ``GET /kv`` serves the
  published slot so the http handler thread NEVER touches engine
  state or its lock.

Gating: everything is default-off behind ``PTPU_MEMOBS`` (enable at
runtime via :func:`enable`); off, every hook returns at one flag read
and counts nothing (``tests/test_memobs.py::
test_memobs_off_gate_counts_nothing``).  Knobs:
``PTPU_MEMOBS_RING`` (timeline ring length, default 512) and
``PTPU_MEMOBS_COOLDOWN_S`` (seconds between kv_pressure dumps,
default 30).  stdlib-only, no jax, like every monitor sibling.
"""
from __future__ import annotations

import math
import os
import threading
import time
from collections import deque

from . import counter as _counter
from . import gauge as _gauge
from . import histogram as _histogram

__all__ = [
    "enabled", "enable", "refresh", "reset",
    "EVENTS", "KVAccounting", "fragmentation", "refcount_histogram",
    "sample", "host_rss_bytes", "timeline_snapshot", "timeline_report",
    "StormDetector", "PressureReporter", "reporter", "rank_holders",
    "build_kv_snapshot", "publish_kv", "maybe_publish_kv", "latest_kv",
    "kv_report",
]

_DEFAULT_RING = 512
_DEFAULT_COOLDOWN_S = 30.0
# /kv pool-map rebuild cadence: the per-step publish check is one
# monotonic read; the O(num_blocks) snapshot build runs at most this
# often (the first call publishes immediately)
KV_PUBLISH_INTERVAL_S = 0.5


def _env_enabled() -> bool:
    return os.environ.get("PTPU_MEMOBS", "").strip().lower() in (
        "1", "true", "on")


_enabled = _env_enabled()


def enabled() -> bool:
    return _enabled


def enable(on: bool = True):
    """Flip the memory microscope on/off at runtime (overrides
    PTPU_MEMOBS)."""
    global _enabled
    _enabled = bool(on)


def refresh():
    """Re-read PTPU_MEMOBS (+ ring knob) from the environment."""
    global _enabled
    _enabled = _env_enabled()
    with _tl_lock:
        _timeline_ref[0] = deque(_timeline_ref[0], maxlen=_ring_len())


# -- (a) KV block-lifecycle accounting ---------------------------------------

# every pool transition, per block.  Events overlap by design — a CoW
# counts one "cow" AND the "alloc" of its fresh block; a swap_in counts
# its blocks under "swap_in" AND "alloc" — each stream answers its own
# question (how much CoW traffic? how fast does the pool cycle?).
EVENTS = ("alloc", "free", "fork", "cow", "park", "adopt", "evict",
          "swap_out", "swap_in")


class KVAccounting:
    """Per-pool lifecycle ledger: plain-int event counts (exact, for
    tests and dumps) twinned with ``serving/kv_blocks{event}`` monitor
    counters.  Every hook checks the module gate first — one global
    read when PTPU_MEMOBS is off."""

    __slots__ = ("events", "_m", "_resid")

    def __init__(self):
        self.events = dict.fromkeys(EVENTS, 0)
        fam = _counter("serving/kv_blocks",
                       "KV pool block transitions, by lifecycle event")
        self._m = {e: fam.labels(event=e) for e in EVENTS}
        self._resid = _histogram(
            "serving/kv_parked_residency_age",
            "seconds a parked prefix block stayed adoptable before "
            "being reclaimed (observed at eviction)")

    def on(self, event: str, n: int = 1) -> None:
        if not _enabled or n <= 0:
            return
        self.events[event] += n
        self._m[event].inc(n)

    def observe_residency(self, age_s: float) -> None:
        if not _enabled:
            return
        self._resid.observe(age_s)


def fragmentation(free_ids, num_blocks: int) -> dict:
    """Free-list contiguity: how many maximal runs of consecutive
    physical ids the free list fragments into, the largest run, and
    ``frag = 1 - largest_run / free`` (0.0 = empty or one contiguous
    extent; toward 1.0 = capacity shredded into single blocks — a
    future contiguous-allocation tier would find no extent even with
    plenty of free blocks)."""
    free = len(free_ids)
    if free == 0:
        return {"free": 0, "total": int(num_blocks), "runs": 0,
                "largest_run": 0, "frag": 0.0}
    ids = sorted(int(i) for i in free_ids)
    runs, largest, run = 1, 1, 1
    for a, b in zip(ids, ids[1:]):
        if b == a + 1:
            run += 1
        else:
            runs += 1
            if run > largest:
                largest = run
            run = 1
    if run > largest:
        largest = run
    return {"free": free, "total": int(num_blocks), "runs": runs,
            "largest_run": largest,
            "frag": round(1.0 - largest / free, 6)}


def refcount_histogram(blocks) -> dict:
    """``{refcount: block count}`` over the pool — how widely shared
    the shared blocks actually are (fork fan-out / prefix adoption)."""
    out: dict = {}
    for blk in blocks:
        r = int(blk.ref)
        out[r] = out.get(r, 0) + 1
    return out


# -- (b) HBM/host timeline ---------------------------------------------------

def _ring_len() -> int:
    try:
        return max(8, int(os.environ.get("PTPU_MEMOBS_RING",
                                         str(_DEFAULT_RING))))
    except ValueError:
        return _DEFAULT_RING


_tl_lock = threading.Lock()
# one-slot list so refresh() can resize without tearing readers (deque
# reads/swaps are atomic under the GIL — the reqlog ring pattern)
_timeline_ref = [deque(maxlen=_ring_len())]

_g_hbm_peak = _gauge("memory/hbm_peak_bytes",
                     "latest sampled peak HBM bytes across compiled "
                     "programs (perf capture)")
_g_hbm_in_use = _gauge("memory/hbm_in_use_bytes",
                       "latest sampled live KV-pool bytes "
                       "(blocks_in_use x bytes_per_block)")
_g_host_rss = _gauge("memory/host_rss_bytes",
                     "latest sampled host resident set size")

# rss reads open /proc per call; a short TTL keeps the per-step sample
# at one monotonic read on the fast path
_RSS_TTL_S = 0.2
_rss_cache = [0.0, None]          # [expires_mono, value]


def host_rss_bytes(ttl_s: float = _RSS_TTL_S):
    """Host RSS via the /healthz path (serve._rss_bytes), cached for
    `ttl_s` so per-step timeline sampling does not open /proc every
    step."""
    now = time.monotonic()
    if now < _rss_cache[0]:
        return _rss_cache[1]
    from .serve import _rss_bytes

    val = _rss_bytes()
    _rss_cache[0] = now + max(0.0, float(ttl_s))
    _rss_cache[1] = val
    return val


def sample(hbm_peak=None, hbm_in_use=None, host_rss=None, ts=None):
    """Append one timeline reading (None fields are recorded as null —
    e.g. hbm_peak with the perf capture off) and mirror the latest
    values into the ``memory/...`` gauges."""
    if not _enabled:
        return
    rec = {"ts": round(time.monotonic() if ts is None else ts, 6),
           "hbm_peak": hbm_peak, "hbm_in_use": hbm_in_use,
           "host_rss": host_rss}
    with _tl_lock:
        _timeline_ref[0].append(rec)
    if hbm_peak is not None:
        _g_hbm_peak.set(hbm_peak)
    if hbm_in_use is not None:
        _g_hbm_in_use.set(hbm_in_use)
    if host_rss is not None:
        _g_host_rss.set(host_rss)


def timeline_snapshot() -> list:
    with _tl_lock:
        return list(_timeline_ref[0])


def timeline_report() -> dict:
    """The ``GET /memory/timeline`` document (ring-only read — safe
    from the http handler thread)."""
    readings = timeline_snapshot()
    return {"enabled": _enabled, "maxlen": _ring_len(),
            "n": len(readings), "readings": readings}


# -- (c) pressure forensics --------------------------------------------------

class StormDetector:
    """EWMA mean/variance detector over per-step pool-pressure events
    (evictions + preemption swaps) — the ``LossSpikeDetector`` shape
    re-aimed at eviction storms and swap thrash.

    A healthy pool evicts occasionally; a storm is a step whose event
    count sits ``sigma`` standard deviations above the EWMA baseline
    (and above ``floor`` — absolute noise guard: the very first
    eviction after a quiet warmup is not a storm).  A flagged step is
    NOT folded into the baseline, and ``cooldown`` observations must
    pass between fires so a sustained storm produces a few markers, not
    one per step."""

    __slots__ = ("alpha", "sigma", "warmup", "cooldown", "floor",
                 "_mean", "_var", "_n", "_step", "_last_fire",
                 "_m_events", "_m_storms")

    def __init__(self, alpha: float = 0.2, sigma: float = 4.0,
                 warmup: int = 8, cooldown: int = 16, floor: float = 2.0):
        self.alpha = float(alpha)
        self.sigma = float(sigma)
        self.warmup = int(warmup)
        self.cooldown = int(cooldown)
        self.floor = float(floor)
        self._mean = 0.0
        self._var = 0.0
        self._n = 0
        self._step = 0
        self._last_fire = None
        self._m_events = _counter(
            "memory/pressure_events",
            "per-step pool-pressure events fed to the storm detector")
        self._m_storms = _counter(
            "memory/eviction_storms",
            "eviction/swap storms flagged by the EWMA detector")

    def observe(self, events: float, step: int = None) -> "dict | None":
        """Feed one step's pressure-event count; returns a storm-info
        dict when the step fires (and drops a flight breadcrumb), else
        None."""
        try:
            events = float(events)
        except (TypeError, ValueError):
            return None
        if step is None:
            step = self._step
        self._step = step + 1
        if events:
            self._m_events.inc(events)
        storm = None
        if self._n >= self.warmup and events >= self.floor:
            sd = math.sqrt(self._var) if self._var > 0 else 0.0
            if events > self._mean + self.sigma * sd:
                storm = {"kind": "eviction_storm", "events": events,
                         "step": step, "ewma": round(self._mean, 4)}
        if storm is not None:
            if self._last_fire is not None and self.cooldown > 0 and \
                    (step - self._last_fire) < self.cooldown:
                return None   # still inside the cooldown window
            self._last_fire = step
            self._m_storms.inc()
            from . import flight

            flight.note("memory/eviction_storm", **storm)
            return storm
        # only a NON-storm step feeds the baseline (a sustained storm
        # must not drag its own baseline up until it disappears)
        self._n += 1
        a = self.alpha if self._n > 1 else 1.0
        delta = events - self._mean
        self._mean += a * delta
        self._var = (1.0 - a) * (self._var + a * delta * delta)
        return None


def _cooldown_s() -> float:
    try:
        return max(0.0, float(os.environ.get(
            "PTPU_MEMOBS_COOLDOWN_S", str(_DEFAULT_COOLDOWN_S))))
    except ValueError:
        return _DEFAULT_COOLDOWN_S


class PressureReporter:
    """Rate-limited ``kv_pressure`` flight dumps.  An admission-failure
    loop or an eviction storm triggers per step; the reporter lets ONE
    dump through per ``cooldown_s`` window (suppressions are counted,
    and consume nothing).  Dumps ride :func:`flight.maybe_dump` — no
    PTPU_FLIGHT_DIR, no file — and are replica-tagged with the
    process's fleet identity."""

    __slots__ = ("cooldown_s", "triggers", "_last_fire", "_m_dumps",
                 "_m_supp")

    def __init__(self, cooldown_s: float = None):
        self.cooldown_s = (_cooldown_s() if cooldown_s is None
                           else float(cooldown_s))
        self.triggers = 0
        self._last_fire = None
        self._m_dumps = _counter(
            "memory/pressure_dumps",
            "kv_pressure flight dumps written (rate-limited)")
        self._m_supp = _counter(
            "memory/pressure_suppressed",
            "kv_pressure triggers suppressed by the dump rate limit")

    def maybe_dump(self, trigger: str, extra: dict = None,
                   now: float = None) -> "str | None":
        """One rate-limited dump attempt; returns the dump path, or
        None (rate-limited, or PTPU_FLIGHT_DIR unset)."""
        from . import flight
        from .serve import identity

        now = time.monotonic() if now is None else now
        self.triggers += 1
        if self._last_fire is not None and \
                now - self._last_fire < self.cooldown_s:
            self._m_supp.inc()
            return None
        self._last_fire = now
        doc = {"trigger": trigger, "replica": identity()}
        if extra:
            doc.update(extra)
        path = flight.maybe_dump("kv_pressure", extra=doc)
        if path:
            self._m_dumps.inc()
        return path


_reporter_ref = [None]


def reporter() -> PressureReporter:
    """The process-wide rate limiter (one cooldown window per process —
    a storm must produce one dump no matter how many triggers see it)."""
    if _reporter_ref[0] is None:
        _reporter_ref[0] = PressureReporter()
    return _reporter_ref[0]


def rank_holders(cache, requests, now: float = None, top: int = 8) -> dict:
    """Ranked memory holders for a ``kv_pressure`` dump / the ``/kv``
    pool map:

    - ``requests``: by ``blocks held x (1 + age_s)`` — the long-held
      large holding outranks both the fresh large and the old small;
    - ``parked_chains``: parked prefix chains (grouped by the chain id
      ``register_prefix`` stamps) by oldest residency;
    - ``tenants``: blocks held per tenant with pool share.

    Reads only host-side dicts (no device sync); call from the engine
    thread."""
    now_pc = time.perf_counter() if now is None else now
    mono = time.monotonic()
    reqs = []
    tenants: dict = {}
    for r in requests:
        table = cache._tables.get(r.req_id)
        if not table:
            continue
        blocks = len(table)
        arr = getattr(r, "arrival_t", None)
        age = max(0.0, now_pc - arr) if arr is not None else 0.0
        tenant = getattr(getattr(r, "params", None), "tenant", None)
        reqs.append({
            "rid": r.req_id,
            "blocks": blocks,
            "age_s": round(age, 3),
            "score": round(blocks * (1.0 + age), 3),
            "tenant": tenant,
            "priority": getattr(getattr(r, "params", None), "priority",
                                None),
        })
        key = tenant or "default"
        tenants[key] = tenants.get(key, 0) + blocks
    reqs.sort(key=lambda d: (-d["score"], -d["blocks"], d["rid"]))
    chains: dict = {}
    for idx, parked_ts in getattr(cache, "_lru", {}).items():
        chain = getattr(cache, "_chain_of", {}).get(idx, "?")
        age = max(0.0, mono - parked_ts) if parked_ts else 0.0
        rec = chains.setdefault(chain, {"chain": chain, "blocks": 0,
                                        "oldest_age_s": 0.0})
        rec["blocks"] += 1
        if age > rec["oldest_age_s"]:
            rec["oldest_age_s"] = round(age, 3)
    parked = sorted(chains.values(),
                    key=lambda d: (-d["oldest_age_s"], -d["blocks"]))
    total = max(getattr(cache, "num_blocks", 0), 1)
    tenant_rows = sorted(
        ({"tenant": t, "blocks": n, "share": round(n / total, 4)}
         for t, n in tenants.items()),
        key=lambda d: (-d["blocks"], d["tenant"]))
    return {"requests": reqs[:top], "parked_chains": parked[:top],
            "tenants": tenant_rows}


# -- (d)/(a) pool-map publication (GET /kv) ----------------------------------

_kv_ref = [None]
_kv_pub_t = [None]


def build_kv_snapshot(cache, requests, now: float = None) -> dict:
    """The structured ``/kv`` pool map: counts, fragmentation, ranked
    holders, parked chains by age, and the refcount histogram.  Built
    on the ENGINE thread and published via :func:`publish_kv` — the
    http handler only ever reads the published document."""
    c = cache.counts()
    doc = {
        "ts": round(time.monotonic(), 6),
        "num_blocks": c["total"],
        "block_size": cache.block_size,
        "bytes_per_block": cache.bytes_per_block,
        "free": c["free"],
        "parked": c["parked"],
        "in_use": c["in_use"],
        "referenced": c["referenced"],
        "allocatable": c["allocatable"],
        "peak_in_use": c["peak_in_use"],
        "utilization": round(c["in_use"] / max(c["total"], 1), 6),
        "fragmentation": fragmentation(cache._free, c["total"]),
        "refcounts": {str(k): v for k, v in sorted(
            refcount_histogram(cache._blocks).items())},
        "events": dict(cache.acct.events),
    }
    doc.update(rank_holders(cache, requests, now=now))
    return doc


def publish_kv(snap: dict) -> None:
    _kv_ref[0] = snap
    _kv_pub_t[0] = time.monotonic()


def maybe_publish_kv(build, now: float = None) -> bool:
    """Interval-limited publication: calls ``build()`` (and publishes
    the result) at most every ``KV_PUBLISH_INTERVAL_S``; the fast path
    is one monotonic read.  First call publishes immediately."""
    if not _enabled:
        return False
    now = time.monotonic() if now is None else now
    t = _kv_pub_t[0]
    if t is not None and now - t < KV_PUBLISH_INTERVAL_S:
        return False
    _kv_ref[0] = build()
    _kv_pub_t[0] = now
    return True


def latest_kv() -> "dict | None":
    return _kv_ref[0]


def kv_report() -> dict:
    """The ``GET /kv`` document (published-slot read only — safe from
    the http handler thread; never touches engine state)."""
    return {"enabled": _enabled, "snapshot": _kv_ref[0]}


def reset() -> None:
    """Test hook: clear the timeline ring, published pool map, and the
    process-wide pressure reporter (counters live in the monitor
    registry and reset with it)."""
    with _tl_lock:
        _timeline_ref[0] = deque(maxlen=_ring_len())
    _kv_ref[0] = None
    _kv_pub_t[0] = None
    _reporter_ref[0] = None
    _rss_cache[0] = 0.0
    _rss_cache[1] = None
