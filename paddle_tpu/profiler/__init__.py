"""Profiler (reference: python/paddle/profiler/profiler.py:344 +
paddle/fluid/platform/profiler/ HostTracer/CudaTracer).

TPU-native: host spans use a lightweight in-process tracer (chrome-trace
exportable, the HostTracer analog); device side delegates to jax.profiler
(XLA xplane capture, viewable in TensorBoard/Perfetto — the CUPTI analog).
"""
from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager

from ..monitor import trace as _mtrace

__all__ = [
    "Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
    "make_scheduler", "export_chrome_tracing", "load_profiler_result",
]


class ProfilerTarget:
    CPU = "cpu"
    GPU = "gpu"
    CUSTOM_DEVICE = "tpu"
    TPU = "tpu"


class ProfilerState:
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


class _HostTracer(threading.local):
    def __init__(self):
        self.events = []
        self.enabled = False


_tracer = _HostTracer()


class RecordEvent:
    """Host span annotation (reference: platform::RecordEvent,
    profiler/event_tracing.h:49). Inside a profiler session the span also
    lands in the xplane capture (`monitor.trace.annotation`)."""

    def __init__(self, name, event_type=None):
        self.name = name
        self._jax_ctx = None
        self._t0 = None

    def begin(self):
        self._t0 = time.perf_counter_ns()
        self._jax_ctx = _mtrace.annotation(self.name)
        if self._jax_ctx is not None:
            self._jax_ctx.__enter__()

    def end(self):
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(None, None, None)
        if _tracer.enabled and self._t0 is not None:
            _tracer.events.append(
                {
                    "name": self.name,
                    "ph": "X",
                    "ts": self._t0 / 1000.0,
                    "dur": (time.perf_counter_ns() - self._t0) / 1000.0,
                    "pid": os.getpid(),
                    "tid": threading.get_ident() % 1_000_000,
                }
            )

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    def scheduler(step):
        step -= skip_first
        if step < 0:
            return ProfilerState.CLOSED
        cycle = closed + ready + record
        if repeat and step >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = step % cycle if cycle else 0
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        fname = os.path.join(
            dir_name, f"{worker_name or 'paddle_tpu'}_{int(time.time())}.json"
        )
        prof._export_chrome(fname)
        return fname

    return handler


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False):
        self._scheduler = scheduler if callable(scheduler) else None
        if isinstance(scheduler, (tuple, list)):
            lo, hi = scheduler
            self._scheduler = make_scheduler(closed=lo, ready=0, record=hi - lo, repeat=1)
        self._on_trace_ready = on_trace_ready
        self._step = 0
        self._xla_dir = None
        self._timer_only = timer_only
        self._step_times = []
        self._last_step_t = None
        self._profile_memory = profile_memory
        self._mem_samples = []  # (bytes_in_use, peak_bytes_in_use) per step
        self._last_trace_dir = None  # xplane dir of the finished capture

    def start(self):
        _tracer.enabled = True
        _tracer.events = []
        self._last_step_t = time.perf_counter()
        if not self._timer_only:
            try:
                import jax.profiler

                self._xla_dir = os.environ.get("PTPU_PROF_DIR", "/tmp/ptpu_profile")
                jax.profiler.start_trace(self._xla_dir)
            except Exception:
                self._xla_dir = None

    def stop(self):
        _tracer.enabled = False
        if self._xla_dir is not None:
            try:
                import jax.profiler

                jax.profiler.stop_trace()
                self._last_trace_dir = self._xla_dir
            except Exception:  # ptpu-check[silent-except]: stop_trace without a matching
                # start raises on some jax versions; profile teardown must not kill the run
                pass
            self._xla_dir = None
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append((now - self._last_step_t, num_samples))
        self._last_step_t = now
        self._step += 1
        if self._profile_memory:
            from .. import device as _device

            self._mem_samples.append((_device.memory_allocated(),
                                      _device.max_memory_allocated()))

    def _ips_samples(self):
        """Per-step ips for exactly the steps that reported num_samples —
        each sample paired with ITS OWN step duration (a positional
        times[-len(samples):] pairing mismatches whenever only some steps
        pass num_samples)."""
        return [n / t for t, n in self._step_times if n and t > 0]

    def step_info(self, unit="samples"):
        if not self._step_times:
            return ""
        import numpy as np

        times = np.array([t for t, _ in self._step_times])
        msg = f"avg step {times.mean()*1000:.2f} ms"
        ips = self._ips_samples()
        if ips:
            msg += f", ips {np.mean(ips):.1f} {unit}/s"
        return msg

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False, time_unit="ms"):
        by_name = {}
        for e in _tracer.events:
            agg = by_name.setdefault(e["name"], [0.0, 0])
            agg[0] += e["dur"] / 1000.0
            agg[1] += 1
        lines = [f"{'name':40s} {'calls':>8s} {'total_ms':>12s}"]
        for name, (tot, n) in sorted(by_name.items(), key=lambda kv: -kv[1][0]):
            lines.append(f"{name[:40]:40s} {n:8d} {tot:12.3f}")
        if self._mem_samples:
            # device-memory statistics column (reference:
            # profiler_statistic.py memory tables / memory/stats.h peaks)
            cur = [c for c, _ in self._mem_samples]
            peak = [p for _, p in self._mem_samples]
            mb = 1 / 2**20
            lines.append("")
            lines.append(
                f"{'device memory (MiB)':40s} {'current':>12s} {'peak':>12s}")
            lines.append(
                f"{'  last step':40s} {cur[-1]*mb:12.1f} {peak[-1]*mb:12.1f}")
            lines.append(
                f"{'  max over steps':40s} {max(cur)*mb:12.1f} "
                f"{max(peak)*mb:12.1f}")
        if op_detail:
            dev = self.device_op_summary(time_unit=time_unit)
            if dev:
                lines += ["", dev]
        # always-on stats layer (paddle_tpu.monitor): counters/gauges/
        # histograms recorded by the train/pipeline/MoE/autotune hot paths
        # share names with the RecordEvent spans above.
        from .. import monitor

        mon = monitor.render()
        if mon:
            lines += ["", mon]
        # perf attribution (paddle_tpu.monitor.perf): ranked MFU/roofline
        # table of every analyzed program and sub-step segment — the row
        # with the worst achieved-vs-optimal ratio is the next kernel to
        # optimize.  Empty unless PTPU_PERF accounting recorded anything.
        try:
            from ..monitor import perf as _mperf

            pa = _mperf.report()
        except ImportError:   # standalone monitor load — no perf module
            pa = ""
        if pa:
            lines += ["", pa]
        # training microscope (paddle_tpu.monitor.train): ranked per-layer
        # grad/param/update table from the PTPU_TRAIN_STATS sampled fused
        # reduction — empty unless the optimizer recorded a sample.
        try:
            from ..monitor import train as _mtrain

            ts = _mtrain.report()
        except ImportError:   # standalone monitor load — no train module
            ts = ""
        if ts:
            lines += ["", ts]
        return "\n".join(lines)

    def device_op_summary(self, top=30, time_unit="ms"):
        """Per-op device-time attribution table parsed from the xplane
        capture (reference: profiler_statistic.py operator/kernel
        statistics fed from the CUPTI event tree; here the jax.profiler
        xplane protobuf, decoded without a tensorflow dependency — see
        profiler/xplane.py). Empty string when no device trace exists
        (timer_only mode, or capture failed)."""
        if self._last_trace_dir is None:
            return ""
        from . import xplane

        files = xplane.find_xplane_files(self._last_trace_dir)
        if not files:
            return ""
        planes = []
        for f in files:
            try:
                planes.extend(xplane.parse_xspace(f))
            except (OSError, ValueError, IndexError):
                continue   # truncated/corrupt capture: skip that file
        stats = xplane.op_stats(planes) if planes else {}
        if not stats:
            return ""
        return xplane.format_op_table(stats, top=top, time_unit=time_unit)

    def _export_chrome(self, fname):
        # one timeline: RecordEvent host spans + monitor.trace framework
        # spans (same perf_counter_ns timebase, so Perfetto interleaves
        # them correctly; trace spans carry trace_id/span_id in args)
        from ..monitor import trace as _mtrace

        events = list(_tracer.events) + _mtrace.chrome_events()
        with open(fname, "w") as f:
            json.dump({"traceEvents": events}, f)

    def export(self, path, format="json"):
        self._export_chrome(path)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def load_profiler_result(path):
    """Load an exported trace: chrome-trace JSON, or the pickled raw host
    event list written by export_protobuf (.pkl)."""
    if path.endswith((".pkl", ".pb.pkl")):
        import pickle

        with open(path, "rb") as f:
            return pickle.load(f)
    with open(path) as f:
        return json.load(f)


class SortedKeys:
    """Summary-table sort keys (reference profiler/profiler.py SortedKeys)."""

    CPUTotal = 0
    CPUAvg = 1
    CPUMax = 2
    CPUMin = 3
    GPUTotal = 4
    GPUAvg = 5
    GPUMax = 6
    GPUMin = 7


class SummaryView:
    """Summary views (reference profiler/profiler.py SummaryView)."""

    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


def export_protobuf(dir_name, worker_name=None):
    """on_trace_ready handler writing the raw trace records (reference
    export_protobuf; here the host-tracer event list is serialized with
    pickle next to the chrome trace — the xplane protobuf itself is
    produced by jax.profiler when the device tracer is active)."""
    import os
    import pickle
    import socket
    import time as _time

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{socket.gethostname()}"
        path = os.path.join(
            dir_name, f"{name}_{int(_time.time() * 1000)}.pb.pkl")
        # the raw records live on the module host tracer, not the Profiler
        # (a prior version pickled a nonexistent prof._events — always [])
        with open(path, "wb") as f:
            pickle.dump(list(_tracer.events), f)
        return path

    return handler


__all__ += ["SortedKeys", "SummaryView", "export_protobuf"]
