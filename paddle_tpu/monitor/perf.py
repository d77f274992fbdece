"""Performance attribution — MFU/roofline accounting on XLA's own
compile-time analyses (the *how close to the hardware* half of the
monitor subsystem; PR-1 metrics say how much, PR-5 traces say where,
this module says how far from optimal).

Three data sources, one registry:

- **compiled-program accounting** — for every compiled step program the
  jit layer (``jit.CompiledFunction``) and the serving engine hand this
  module XLA's ``cost_analysis()`` (flops, bytes accessed) and
  ``memory_analysis()`` (argument/output/temp/generated-code bytes).
  Combined with the chip's peak numbers (``chip_spec()``) and measured,
  **synced** wall time per call, each program gets: achieved FLOP/s,
  MFU vs the bf16 peak, arithmetic intensity vs the roofline ridge
  (compute- vs memory-bound), the roofline-optimal step time, and the
  achieved-vs-optimal ratio — the number a perf PR must move.
- **step-segment breakdown** — named, properly-synced sub-step timers:
  ``hapi.Model`` splits the eager train step into
  forward/backward/optimizer.  The serving step itself is NOT synced for
  timing: its host phases are the always-on ``serving/host_time{phase}``
  histogram (``monitor.trace.phase``), which ``report()`` appends.
- **HBM attribution** — per-program peak-bytes estimate and headroom vs
  the chip's HBM (``perf/hbm_headroom``), the memfit gate's live twin.

Gate: ``PTPU_PERF=1`` (default OFF — perf mode syncs after every timed
call and routes fresh compiles through the AOT path to capture their
analyses, both of which perturb steady-state pipelining; it is a
diagnostic mode, not an always-on tax).  With the gate off every hook
is one module-global read: a disabled segment is the shared no-op
object (tests/test_perf.py), held to < 1 µs a call by
tests/test_disabled_overhead.py.

Import constraints (shared with trace/flight/serve): importing this
module never imports jax — analyses arrive as plain dicts/objects from
callers that already hold jax, and the jax bits (``measure()``, chip
detection, ``block_until_ready``) import lazily inside functions.

Exported metrics (all literal, lint_metrics-clean):
``perf/mfu`` (overall, callback), ``perf/mfu{fn}``, ``perf/flops{fn}``,
``perf/bytes{fn}``, ``perf/hbm_peak_bytes{fn}``,
``perf/hbm_headroom{fn}``, ``perf/analysis_unavailable{fn}``,
``perf/step_time{fn}`` (histogram), ``perf/segment_time{step,segment}``
(histogram), ``perf/capture_errors{site}``, ``perf/cost_keys_dropped``.
"""
from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict

__all__ = [
    "enabled", "enable", "refresh", "chip_spec", "ChipSpec",
    "normalize_cost_analysis", "capture", "observe", "observe_segment",
    "segment", "measure", "records", "get", "report", "hlo_report",
    "reset", "UNAVAILABLE",
]

UNAVAILABLE = "unavailable"


def _env_enabled() -> bool:
    return os.environ.get("PTPU_PERF", "0").strip().lower() not in (
        "0", "false", "off", "")


_enabled = _env_enabled()


def enabled() -> bool:
    return _enabled


def enable(on: bool = True):
    """Flip perf accounting on/off at runtime (overrides PTPU_PERF)."""
    global _enabled
    _enabled = bool(on)


def refresh():
    """Re-read PTPU_PERF from the environment."""
    global _enabled
    _enabled = _env_enabled()


def _registry():
    from . import get_registry

    return get_registry()


# -- chip model -------------------------------------------------------------

class ChipSpec:
    """Peak numbers the roofline is drawn against.  ``peak_flops`` is the
    dense bf16 (MXU) peak in FLOP/s, ``hbm_bw`` bytes/s, ``hbm_bytes``
    per-device HBM capacity.  Env overrides (for A/B or odd hosts):
    PTPU_PERF_PEAK_FLOPS, PTPU_PERF_HBM_GBS (GB/s), PTPU_PERF_HBM_GIB."""

    __slots__ = ("name", "peak_flops", "hbm_bw", "hbm_bytes")

    def __init__(self, name, peak_flops, hbm_bw, hbm_bytes):
        self.name = name
        self.peak_flops = float(peak_flops)
        self.hbm_bw = float(hbm_bw)
        self.hbm_bytes = float(hbm_bytes)

    @property
    def ridge(self) -> float:
        """Roofline ridge point (FLOP/byte): programs above it are
        compute-bound, below it memory-bound."""
        return self.peak_flops / max(self.hbm_bw, 1.0)

    def __repr__(self):
        return (f"ChipSpec({self.name}, {self.peak_flops/1e12:.0f} TFLOP/s,"
                f" {self.hbm_bw/1e9:.0f} GB/s, "
                f"{self.hbm_bytes/2**30:.0f} GiB)")


# (peak bf16 FLOP/s, HBM bytes/s, HBM bytes) — v5e's FLOP/s and HBM
# bandwidth match benchmark/lib/peaks.json, so MFU here and the cells'
# rooflines agree.
_KNOWN_CHIPS = (
    ("v5 lite", ("tpu-v5e", 197e12, 819e9, 16 * 2**30)),
    ("v5e", ("tpu-v5e", 197e12, 819e9, 16 * 2**30)),
    ("v5p", ("tpu-v5p", 459e12, 2765e9, 95 * 2**30)),
    ("v4", ("tpu-v4", 275e12, 1228e9, 32 * 2**30)),
    ("v3", ("tpu-v3", 123e12, 900e9, 16 * 2**30)),
)


def _host_ram_bytes() -> float:
    try:
        return float(os.sysconf("SC_PHYS_PAGES") * os.sysconf("SC_PAGE_SIZE"))
    except (ValueError, OSError, AttributeError):
        return 16 * 2**30


_chip = None
_chip_lock = threading.Lock()


def chip_spec(refresh_probe: bool = False) -> ChipSpec:
    """The current backend's ChipSpec (probed once, cached).  CPU hosts
    get stand-in peaks, and HBM capacity falls back to host RAM — the
    numbers still rank segments correctly relative to each other, which
    is what the attribution table is for.  A TPU whose ``device_kind``
    is not in ``_KNOWN_CHIPS`` is an error: no peaks are assumed for
    it."""
    global _chip
    if _chip is not None and not refresh_probe:
        return _chip
    with _chip_lock:
        if _chip is not None and not refresh_probe:
            return _chip
        import jax

        dev = jax.devices()[0]
        if dev.platform == "cpu":
            name, peak, bw, cap = "cpu", 5e9, 50e9, _host_ram_bytes()
        else:
            kind = dev.device_kind.lower()
            for marker, spec in _KNOWN_CHIPS:
                if marker in kind:
                    name, peak, bw, cap = spec
                    break
            else:
                raise ValueError(
                    f"chip_spec: no peaks known for device_kind "
                    f"{dev.device_kind!r} (platform {dev.platform!r}); "
                    "add it to _KNOWN_CHIPS with its source")
        peak = float(os.environ.get("PTPU_PERF_PEAK_FLOPS", peak))
        bw = float(os.environ.get("PTPU_PERF_HBM_GBS", bw / 1e9)) * 1e9
        cap = float(os.environ.get("PTPU_PERF_HBM_GIB", cap / 2**30)) * 2**30
        _chip = ChipSpec(name, peak, bw, cap)
        return _chip


# -- analysis normalization -------------------------------------------------

def normalize_cost_analysis(analysis):
    """XLA's ``cost_analysis()`` across jax versions returns a dict, a
    one-element list of dicts, or None; entries may be non-scalar
    (utilization maps).  Returns ``(cost, dropped)``: scalar-only dict
    plus the count of non-scalar entries it had to drop — counted, never
    silent (the CostModel bug this module dedupes away)."""
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return {}, 0
    cost, dropped = {}, 0
    for k, v in analysis.items():
        if isinstance(v, bool):
            dropped += 1
        elif isinstance(v, (int, float)):
            cost[str(k)] = float(v)
        else:
            dropped += 1
    return cost, dropped


def _memory_dict(mem) -> dict:
    """CompiledMemoryStats → plain dict + derived peak estimate (the
    memfit gate's formula: arguments + temps − aliased)."""
    if isinstance(mem, dict):
        out = {k: int(v) for k, v in mem.items()
               if isinstance(v, (int, float)) and not isinstance(v, bool)}
    else:
        out = {}
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            v = getattr(mem, k, None)
            if isinstance(v, (int, float)):
                out[k] = int(v)
    if out and "peak_bytes_estimate" not in out:
        out["peak_bytes_estimate"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
    return out


# -- the per-program record -------------------------------------------------

class FnPerf:
    """One compiled program's (or named segment's) accounting: what XLA
    says it must do (cost/memory) and what the host measured it doing
    (synced wall times)."""

    __slots__ = ("label", "cost", "memory", "dropped_keys",
                 "calls", "total_s", "min_s", "last_s")

    def __init__(self, label):
        self.label = label
        self.cost = {}
        self.memory = {}
        self.dropped_keys = 0
        self.calls = 0
        self.total_s = 0.0
        self.min_s = float("inf")
        self.last_s = 0.0

    # -- what XLA promised -------------------------------------------------
    @property
    def flops(self):
        return self.cost.get("flops")

    @property
    def bytes_accessed(self):
        return self.cost.get("bytes accessed")

    @property
    def available(self) -> bool:
        """True when the analysis yielded usable flops OR bytes —
        zero-flop programs (pure copy/scatter, e.g. a paged cache
        update) are legitimately memory-roofline-only and must still
        rank.  CPU/stat-less backends can return empty dicts — those
        records stay visible but every derived figure reads
        'unavailable' instead of garbage."""
        f, b = self.flops, self.bytes_accessed
        return bool((f and f > 0) or (b and b > 0))

    @property
    def peak_bytes(self):
        return self.memory.get("peak_bytes_estimate")

    @property
    def intensity(self):
        """Arithmetic intensity, FLOP per HBM byte (0.0 for a zero-flop
        copy program — maximally memory-bound, not unavailable)."""
        if self.flops is None or not self.bytes_accessed:
            return None
        return self.flops / self.bytes_accessed

    def bound(self, chip=None) -> str:
        ai = self.intensity
        if ai is None:
            return UNAVAILABLE
        chip = chip or chip_spec()
        return "compute" if ai >= chip.ridge else "memory"

    def optimal_s(self, chip=None):
        """Roofline-optimal wall time: the max of pure-compute and
        pure-bandwidth lower bounds (a zero-flop program's bound is
        purely bandwidth)."""
        if not self.available:
            return None
        chip = chip or chip_spec()
        t = (self.flops or 0.0) / chip.peak_flops
        if self.bytes_accessed:
            t = max(t, self.bytes_accessed / chip.hbm_bw)
        return t or None

    # -- what the host measured --------------------------------------------
    def add_wall(self, wall_s: float):
        self.calls += 1
        self.total_s += wall_s
        self.min_s = min(self.min_s, wall_s)
        self.last_s = wall_s

    @property
    def best_s(self):
        return self.min_s if self.calls else None

    def mfu(self, chip=None):
        """Achieved fraction of the chip's bf16 peak at the BEST observed
        wall time (min-of-N: host noise only ever slows a step down).
        None for zero-flop programs — their roofline figure is
        achieved_vs_optimal, not MFU."""
        if not self.flops or not self.calls or self.min_s <= 0:
            return None
        chip = chip or chip_spec()
        return self.flops / self.min_s / chip.peak_flops

    def achieved_vs_optimal(self, chip=None):
        """optimal/achieved in (0, 1]; 1.0 = running at the roofline.
        The ranking key of the attribution table — the segment with the
        SMALLEST ratio is the next optimization target.  Clamped at 1.0:
        a stand-in chip spec (CPU hosts) can under-state the real peaks,
        and a raw ratio above 1 would just mean "spec too low", not
        "faster than the roofline"."""
        opt = self.optimal_s(chip)
        if opt is None or not self.calls or self.min_s <= 0:
            return None
        return min(1.0, opt / self.min_s)

    def hbm_headroom(self, chip=None):
        pk = self.peak_bytes
        if not pk or pk <= 0:
            return None
        chip = chip or chip_spec()
        return chip.hbm_bytes / pk

    def as_dict(self) -> dict:
        chip = chip_spec()
        return {
            "label": self.label,
            "available": self.available,
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "intensity": self.intensity,
            "bound": self.bound(chip),
            "calls": self.calls,
            "wall_best_s": self.best_s,
            "wall_avg_s": (self.total_s / self.calls) if self.calls
            else None,
            "mfu": self.mfu(chip),
            "optimal_s": self.optimal_s(chip),
            "achieved_vs_optimal": self.achieved_vs_optimal(chip),
            "peak_bytes": self.peak_bytes,
            "hbm_headroom": self.hbm_headroom(chip),
            "memory": dict(self.memory),
            "dropped_cost_keys": self.dropped_keys,
        }


_records: "OrderedDict[str, FnPerf]" = OrderedDict()
_rec_lock = threading.Lock()
# dispatched-flops / synced-wall totals behind the overall perf/mfu gauge
_totals = {"flops": 0.0, "wall_s": 0.0}
_mfu_gauge_registered = False


def _overall_mfu() -> float:
    w = _totals["wall_s"]
    if w <= 0:
        return 0.0
    return _totals["flops"] / w / chip_spec().peak_flops


def _ensure_overall_gauge():
    global _mfu_gauge_registered
    if not _mfu_gauge_registered:
        _mfu_gauge_registered = True
        _registry().gauge(
            "perf/mfu",
            "achieved fraction of chip bf16 peak, all analyzed programs",
            fn=_overall_mfu)


def _get_record(label: str) -> FnPerf:
    with _rec_lock:
        rec = _records.get(label)
        if rec is None:
            rec = _records[label] = FnPerf(label)
        return rec


def _match_record(label: str, cost: dict) -> FnPerf:
    """The record for `label` whose analysis matches `cost` — two DIFFERENT
    programs sharing a label (a recompiled step at a new batch shape, two
    '<lambda>'s through CostModel) must not merge, or wall times measured
    on one program get ratioed against the other's flops and the MFU /
    ach-opt ranking is fiction.  The first distinct program keeps the bare
    label; later ones get `label#2`, `label#3`, ...  An empty `cost`
    (stat-less backend) reuses the base record, as does a matching one."""
    with _rec_lock:
        base, i = label, 1
        while True:
            rec = _records.get(label)
            if rec is None:
                rec = _records[label] = FnPerf(label)
                return rec
            if not cost or not rec.cost or rec.cost == cost:
                return rec
            i += 1
            label = f"{base}#{i}"


def records() -> list:
    """Every FnPerf record, insertion-ordered."""
    with _rec_lock:
        return list(_records.values())


def get(label: str):
    with _rec_lock:
        return _records.get(label)


def reset():
    """Drop every record (incl. captured HLO analyses) and zero the MFU
    totals (tests)."""
    with _rec_lock:
        _records.clear()
        _totals["flops"] = 0.0
        _totals["wall_s"] = 0.0
    from . import hlo as _hlo

    _hlo.reset()


# -- capture / observe ------------------------------------------------------

def capture(label, lowered=None, compiled=None, cost=None, memory=None):
    """Attach XLA's analyses to `label`'s record and export the static
    gauges.  Accepts the jax AOT objects (``lowered``/``compiled``) or
    pre-extracted dicts; every probe failure is counted, never raised —
    a backend without analysis support leaves the record marked
    unavailable, and derived gauges (mfu/headroom) are simply not set
    (the graceful-degradation contract of tests/test_perf.py).

    Returns the record the analyses landed in — a DIFFERENT program
    under the same label (see ``_match_record``) gets a ``label#N``
    record, so callers must route subsequent ``observe()`` calls via
    ``rec.label``, not the label they passed in."""
    m = _registry()
    if cost is None:
        for site, obj in (("compiled", compiled), ("lowered", lowered)):
            if obj is None:
                continue
            try:
                cost = obj.cost_analysis()
                break
            except Exception:   # ptpu-check[silent-except]: analysis support varies by
                # backend/jax version; counted, record stays unavailable
                m.counter("perf/capture_errors",
                          "failed analysis/probe captures").labels(
                    site=f"cost_{site}").inc()
    if memory is None and compiled is not None:
        try:
            memory = compiled.memory_analysis()
        except Exception:   # ptpu-check[silent-except]: same contract as cost above
            m.counter("perf/capture_errors",
                      "failed analysis/probe captures").labels(
                site="memory").inc()
    norm, dropped = normalize_cost_analysis(cost)
    rec = _match_record(label, norm)
    label = rec.label
    if norm:
        rec.cost = norm
    rec.dropped_keys += dropped
    if dropped:
        m.counter("perf/cost_keys_dropped",
                  "non-scalar cost_analysis entries skipped").inc(dropped)
    if memory is not None:
        md = _memory_dict(memory)
        if md:
            rec.memory = md
    chip = chip_spec()
    if rec.available:
        if rec.flops is not None:
            m.gauge("perf/flops",
                    "XLA cost-analysis FLOPs per call").labels(
                fn=label).set(rec.flops)
        if rec.bytes_accessed:
            m.gauge("perf/bytes",
                    "XLA cost-analysis HBM bytes per call").labels(
                fn=label).set(rec.bytes_accessed)
        # a prior failed capture may have flagged this fn unavailable;
        # the marker must not outlive the condition it reports
        m.gauge("perf/analysis_unavailable",
                "1 = backend returned no usable cost analysis").labels(
            fn=label).set(0)
    else:
        m.gauge("perf/analysis_unavailable",
                "1 = backend returned no usable cost analysis").labels(
            fn=label).set(1)
    pk = rec.peak_bytes
    if pk and pk > 0:
        m.gauge("perf/hbm_peak_bytes",
                "compile-time peak live bytes estimate").labels(
            fn=label).set(pk)
        m.gauge("perf/hbm_headroom",
                "chip HBM / compile-time peak bytes").labels(
            fn=label).set(chip.hbm_bytes / pk)
    # ISSUE 12: HLO-level kernel attribution off the SAME executable this
    # signature's one AOT compile already produced — text only, parsed by
    # the stdlib hlo module; any failure degrades to an unavailable
    # record (counted), never a broken capture
    if _enabled and compiled is not None:
        from . import hlo as _hlo

        text = None
        try:
            text = compiled.as_text()
        except Exception:   # ptpu-check[silent-except]: as_text support varies by
            # backend/jax version; the program-level analyses above stand
            m.counter("perf/capture_errors",
                      "failed analysis/probe captures").labels(
                site="hlo_text").inc()
        if text is not None:
            _hlo.capture(label, text)
    _ensure_overall_gauge()
    return rec


def observe(label: str, wall_s: float):
    """Record one synced call of `label` taking ``wall_s`` seconds and
    refresh its derived gauges."""
    m = _registry()
    rec = _get_record(label)
    rec.add_wall(wall_s)
    m.histogram("perf/step_time",
                "synced wall seconds per analyzed program").labels(
        fn=label).observe(wall_s)
    if rec.available:
        with _rec_lock:   # += is a read-modify-write: two perf-on
            # threads would otherwise lose increments and drift the
            # overall perf/mfu callback gauge
            _totals["flops"] += rec.flops or 0.0
            _totals["wall_s"] += wall_s
        mfu = rec.mfu()
        if mfu is not None:
            m.gauge("perf/mfu",
                    "achieved fraction of chip bf16 peak, all analyzed "
                    "programs").labels(fn=label).set(mfu)
    _ensure_overall_gauge()
    return rec


def observe_segment(step: str, name: str, wall_s: float):
    """A named sub-step segment's synced wall time
    (forward/backward/optimizer in the eager train step).  Also lands in
    the ``step:name`` record so segments and whole programs share one
    attribution table."""
    _registry().histogram(
        "perf/segment_time",
        "synced sub-step segment seconds").labels(
        step=step, segment=name).observe(wall_s)
    return observe(f"{step}:{name}", wall_s)


class _NoopSegment:
    """The shared disabled-mode segment: no allocation, no state — the
    <1µs disabled-overhead guard is met by not constructing anything."""

    __slots__ = ()

    def sync(self, *objs):
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SEGMENT = _NoopSegment()


class segment:
    """Properly-synced segment timer::

        with perf.segment("train", "forward") as s:
            loss = model(x)
            s.sync(loss)            # block on these arrays at exit

    No-op (one global read + a shared singleton) when perf is disabled.
    ``sync()`` collects arrays/Tensors/pytrees; exit blocks until they
    are device-complete, so the recorded time is the segment's real wall
    time, not its dispatch time."""

    __slots__ = ("_step", "_name", "_t0", "_targets", "_on")

    def __new__(cls, step: str, name: str):
        if not _enabled:
            return _NOOP_SEGMENT
        return object.__new__(cls)

    def __init__(self, step: str, name: str):
        self._on = True
        self._step = step
        self._name = name
        self._targets = []
        self._t0 = None

    def sync(self, *objs):
        if self._on:
            self._targets.extend(objs)
        return self

    def __enter__(self):
        if self._on:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self._t0 is None:
            return False
        if self._targets:
            _block_until_ready(self._targets)
        observe_segment(self._step, self._name,
                        time.perf_counter() - self._t0)
        return False


def _block_until_ready(obj):
    import jax

    def leaf(x):
        data = getattr(x, "_data", x)    # Tensor → array
        if hasattr(data, "block_until_ready"):
            data.block_until_ready()

    jax.tree_util.tree_map(leaf, obj)


# -- one-shot measurement (CostModel / breakdown backend) -------------------

def measure(fn, *arrays, label=None, reps: int = 2, donate_argnums=(),
            static_argnums=(), rearm=None):
    """Lower+compile ``fn`` on ``arrays`` (jax AOT path), capture its
    cost/memory analyses, execute it ``reps``+1 times (first run is
    warmup/page-in) with a full sync, and return the record's
    ``as_dict()`` plus ``wall_time_s`` (best synced run).  The shared
    backend of ``CostModel.profile_measure`` — ONE lower/compile/analyze
    convention instead of hand-rolled ones.

    ``fn`` may already be a ``jax.jit`` object (it is lowered as-is,
    preserving its own donation).  With donation, buffers are re-armed
    between reps: ``rearm(args, out) -> new args`` when given, else the
    single donated position is replaced by the output wholesale (the
    donated-pool ping-pong), else outputs fill donated positions in
    order."""
    import jax

    label = label or getattr(fn, "__name__", "<fn>")
    jitted = fn if hasattr(fn, "lower") else jax.jit(
        fn, donate_argnums=donate_argnums, static_argnums=static_argnums)
    lowered = jitted.lower(*arrays)
    compiled = lowered.compile()
    rec = capture(label, lowered=lowered, compiled=compiled)
    args = tuple(arrays)
    donated = bool(donate_argnums) or rearm is not None
    best = float("inf")
    for _ in range(max(1, int(reps)) + 1):
        t0 = time.perf_counter()
        out = compiled(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
        if rearm is not None:
            args = tuple(rearm(args, out))
        elif donated:
            args = list(args)
            if len(donate_argnums) == 1:
                args[donate_argnums[0]] = out
            else:
                outs = out if isinstance(out, (list, tuple)) else (out,)
                for i, o in zip(donate_argnums, outs):
                    args[i] = o
            args = tuple(args)
    observe(rec.label, best)   # rec.label, not label: a same-named but
    # different program was routed to its own `label#N` record
    result = rec.as_dict()
    result["wall_time_s"] = best
    return result


# -- the attribution table --------------------------------------------------

def _fmt(v, spec="{:.3g}", na="-"):
    return na if v is None else spec.format(v)


def _host_phase_lines() -> list:
    """The serving loop's host phases, most time first: the always-on
    ``serving/host_time{phase}`` histogram that ``monitor.trace.phase``
    feeds (unsynced host clock — the device runs underneath
    ``engine/sample_dispatch`` and is waited for in ``engine/readback``)."""
    snap = _registry().snapshot()
    series = snap.get("serving/host_time") or {}
    cpu = snap.get("serving/host_cpu") or {}
    rows = sorted(((k, v) for k, v in series.items()
                   if k and v.get("count")), key=lambda kv: -kv[1]["sum"])
    if not rows:
        return []
    # cpu_s: the thread's own CPU time inside the phase; what total_s
    # holds beyond it the thread waited (the GIL, its core, the runtime)
    lines = ["serving host phases (serving/host_time and serving/host_cpu, "
             "host clock, not synced):",
             f"  {'phase':28s} {'calls':>6s} {'mean_ms':>9s} {'total_s':>9s} "
             f"{'cpu_s':>9s}"]
    lines += [f"  {key.partition('=')[2][:28]:28s} {v['count']:6d} "
              f"{1e3 * v['sum'] / v['count']:9.3f} {v['sum']:9.3f} "
              f"{cpu.get(key, 0.0):9.3f}"
              for key, v in rows]
    return lines


def report(top: int = 30) -> str:
    """Ranked attribution table (merged into ``Profiler.summary()``):
    programs/segments by total synced wall time, each with its roofline
    classification, MFU, and achieved-vs-optimal ratio.  The row with
    the smallest ach/opt ratio is the next optimization target; rows
    whose backend returned no analysis read 'unavailable' instead of a
    fabricated MFU.  The serving loop's host phases follow."""
    recs = [r for r in records() if r.calls or r.cost or r.memory]
    if not recs:
        return "\n".join(_host_phase_lines())
    chip = chip_spec()
    recs.sort(key=lambda r: -r.total_s)
    lines = [
        f"perf attribution vs {chip.name} "
        f"({chip.peak_flops/1e12:.1f} TFLOP/s, {chip.hbm_bw/1e9:.0f} GB/s,"
        f" ridge {chip.ridge:.1f} flop/B); overall mfu "
        f"{_overall_mfu()*100:.2f}%",
        f"  {'program/segment':28s} {'calls':>6s} {'best_ms':>9s} "
        f"{'gflop':>8s} {'gb':>7s} {'bound':>8s} {'mfu%':>7s} "
        f"{'opt_ms':>8s} {'ach/opt':>8s} {'hbm_room':>8s}",
    ]
    worst = None
    for r in recs[:top]:
        if not r.available:
            wall = _fmt(r.best_s and r.best_s * 1e3, "{:9.3f}", " " * 9)
            lines.append(
                f"  {r.label[:28]:28s} {r.calls:6d} {wall:>9s} "
                f"{'analysis ' + UNAVAILABLE:>42s}")
            continue
        ratio = r.achieved_vs_optimal(chip)
        if ratio is not None and (worst is None or ratio < worst[1]):
            worst = (r.label, ratio)
        mfu = r.mfu(chip)
        lines.append(
            "  {:28s} {:6d} {:>9s} {:>8s} {:>7s} {:>8s} {:>7s} {:>8s} "
            "{:>8s} {:>8s}".format(
                r.label[:28], r.calls,
                _fmt(r.best_s and r.best_s * 1e3, "{:.3f}"),
                _fmt(r.flops and r.flops / 1e9, "{:.2f}"),
                _fmt(r.bytes_accessed and r.bytes_accessed / 1e9,
                     "{:.3f}"),
                r.bound(chip),
                _fmt(mfu and mfu * 100, "{:.2f}"),
                _fmt(r.optimal_s(chip) and r.optimal_s(chip) * 1e3,
                     "{:.3f}"),
                _fmt(ratio, "{:.3f}"),
                _fmt(r.hbm_headroom(chip), "{:.1f}x")))
    if worst is not None:
        lines.append(f"  worst achieved-vs-optimal: {worst[0]} "
                     f"({worst[1]:.3f} of roofline)")
    return "\n".join(lines + _host_phase_lines())


def hlo_report(fn=None, top: int = 10) -> str:
    """The program microscope (ISSUE 12): per-instruction attribution of
    a captured program's optimized HLO — top-k entry instructions (the
    units XLA dispatches) ranked by roofline-model time, fusions called
    out with their estimated flops/bytes.

    ``fn`` may be a perf-record label string, a ``jit.CompiledFunction``
    (its perf label is used), any callable (``__name__``), or None for
    every captured program concatenated.  Programs are captured on the
    same PTPU_PERF AOT path as the cost analyses; a program whose HLO
    text failed to parse renders as 'unavailable' — never invented
    numbers."""
    from . import hlo as _hlo

    if fn is None:
        parts = [_hlo.report(lb, top=top) for lb in _hlo.labels()]
        return "\n".join(p for p in parts if p)
    if isinstance(fn, str):
        label = fn
    elif hasattr(fn, "_perf_label"):
        label = fn._perf_label()
    else:
        label = getattr(fn, "__name__", str(fn))
    return _hlo.report(label, top=top)
