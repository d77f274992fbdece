"""Device management (reference: python/paddle/device/).

The reference juggles Places/DeviceContexts; here devices are jax devices
and `set_device` selects the default placement. On TPU there is no
per-stream API to expose — XLA's async runtime owns scheduling — so the
cuda-stream surface maps to no-ops with documented semantics.
"""
from __future__ import annotations

import jax

_current = None


def get_all_devices():
    return jax.devices()


def device_count():
    return jax.device_count()


def local_device_count():
    return jax.local_device_count()


def set_device(device: str):
    """Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0' (mapped to available backends)."""
    global _current
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    # 'gpu'/'xpu'/... requests map onto the accelerator actually present
    if name in ("tpu", "gpu", "xpu", "npu", "mlu", "custom_cpu"):
        pool = [d for d in jax.devices() if d.platform != "cpu"]
        if not pool:
            raise RuntimeError(
                f"set_device({device!r}): no accelerator on this host "
                f"(jax platform is {jax.devices()[0].platform!r}); ask "
                "for 'cpu' explicitly to run on the host")
    elif name == "cpu":
        try:
            pool = jax.devices("cpu")
        except RuntimeError:
            pool = jax.devices()
    else:
        raise ValueError(f"unknown device {device!r}")
    _current = pool[min(idx, len(pool) - 1)]
    jax.config.update("jax_default_device", _current)
    return _current


def get_device():
    if _current is None:
        d = jax.devices()[0]
    else:
        d = _current
    plat = "tpu" if d.platform not in ("cpu",) else "cpu"
    return f"{plat}:{d.id}"


def is_compiled_with_cuda():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_tpu():
    return any(d.platform != "cpu" for d in jax.devices())


def is_compiled_with_custom_device(name="tpu"):
    return is_compiled_with_tpu()


class Stream:
    """API-compat stream object. XLA orders work internally; recording an
    event maps to a `block_until_ready` fence when synchronized."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()


class Event:
    def __init__(self, enable_timing=False, blocking=False, interprocess=False):
        pass

    def record(self, stream=None):
        pass

    def synchronize(self):
        synchronize()


def synchronize(device=None):
    """Block until all queued work is done (reference:
    paddle.device.cuda.synchronize)."""
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()


def _resolve_device(device=None):
    if device is None:
        return _current if _current is not None else jax.devices()[0]
    if isinstance(device, int):
        return jax.devices()[device]
    if isinstance(device, str):
        idx = int(device.split(":")[1]) if ":" in device else 0
        return jax.devices()[idx]  # out-of-range raises, same as int form
    return device  # already a jax device


def memory_stats(device=None):
    """Raw PJRT allocator statistics for one device (reference:
    paddle/fluid/memory/stats.h surface). Keys include bytes_in_use,
    peak_bytes_in_use, bytes_limit where the backend reports them; an
    empty dict on backends without allocator stats (XLA-CPU)."""
    try:
        stats = _resolve_device(device).memory_stats()
        return dict(stats) if stats else {}
    except Exception:
        return {}


def _mem_stat(key, device=None):
    return int(memory_stats(device).get(key, 0))


_live_peak = 0  # host-tracked watermark for backends without allocator stats
_live_cache = (0.0, 0)          # (monotonic stamp, bytes) of the last sweep
_LIVE_TTL = 0.05                # paired bytes_in_use/peak queries share a sweep
_live_lock = __import__("threading").Lock()


def _live_bytes():
    """Sum of live jax.Array buffer bytes — the fallback 'bytes in use'
    measure on backends whose PJRT client reports no allocator stats
    (XLA-CPU, i.e. the test mesh). PROCESS-WIDE across local devices
    (sharded arrays report their global nbytes; per-device attribution
    needs real allocator stats). Also advances the host-side peak
    watermark so max_memory_allocated stays meaningful there. The O(live
    arrays) sweep is memoized for _LIVE_TTL so the usual paired
    current+peak query costs one sweep, and watermark updates are locked
    (profiler sampling and monitor export run from different threads)."""
    import time as _time

    global _live_peak, _live_cache
    with _live_lock:
        stamp, cached = _live_cache
        now = _time.monotonic()
        if now - stamp < _LIVE_TTL:
            return cached
        try:
            n = sum(int(a.nbytes) for a in jax.live_arrays())
        except Exception:
            n = 0
        _live_cache = (now, n)
        if n > _live_peak:
            _live_peak = n
        return n


def max_memory_allocated(device=None):
    """Peak device-memory bytes in use (reference:
    paddle.device.cuda.max_memory_allocated). On TPU this is the PJRT
    allocator's peak_bytes_in_use — the per-step HBM high-water mark; on
    stat-less backends, the high-water mark of observed live-array bytes."""
    stats = memory_stats(device)
    if "peak_bytes_in_use" in stats:   # key presence, not truthiness: a
        return int(stats["peak_bytes_in_use"])  # real allocator may say 0
    _live_bytes()
    return _live_peak


def memory_allocated(device=None):
    """Current device-memory bytes in use (reference:
    paddle.device.cuda.memory_allocated)."""
    stats = memory_stats(device)
    if "bytes_in_use" in stats:
        return int(stats["bytes_in_use"])
    return _live_bytes()


def max_memory_reserved(device=None):
    """Reference max_memory_reserved: the allocator pool bound — PJRT
    reports the backend's bytes_limit (0 when unreported)."""
    return _mem_stat("bytes_limit", device)


def memory_reserved(device=None):
    return _mem_stat("bytes_reserved", device) or _mem_stat(
        "bytes_in_use", device)


cuda = type(
    "cuda_ns",
    (),
    {
        "Stream": Stream,
        "Event": Event,
        "synchronize": staticmethod(synchronize),
        "device_count": staticmethod(device_count),
        "max_memory_allocated": staticmethod(max_memory_allocated),
        "memory_allocated": staticmethod(memory_allocated),
        "max_memory_reserved": staticmethod(max_memory_reserved),
        "memory_reserved": staticmethod(memory_reserved),
        "empty_cache": staticmethod(lambda: None),
    },
)()


def get_all_device_type():
    """Device types visible to the runtime (reference
    device.get_all_device_type)."""
    import jax

    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return [t for t in get_all_device_type() if t not in ("cpu", "gpu")]


def get_available_device():
    import jax

    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [d for d in get_available_device()
            if not d.startswith(("cpu", "gpu"))]


def get_cudnn_version():
    """No cuDNN on this backend (reference returns None when not compiled
    with CUDA)."""
    return None


def is_compiled_with_cinn():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_mlu():
    return False


def is_compiled_with_npu():
    return False


def is_compiled_with_xpu():
    return False


from .. import monitor as _monitor  # noqa: E402

# Always-on memory watermark series (reference STAT_INT memory gauges fed
# from memory/stats.h). Callback gauges: sampled only at snapshot/export,
# zero steady-state cost.
_monitor.gauge("device/peak_bytes",
               help="peak device memory bytes in use",
               fn=max_memory_allocated)
_monitor.gauge("device/bytes_in_use",
               help="current device memory bytes in use",
               fn=memory_allocated)
_monitor.gauge("device/bytes_limit",
               help="allocator pool bound (0 when unreported)",
               fn=max_memory_reserved)

from ..framework.compat import XPUPlace, CustomPlace as _CustomPlace  # noqa: E402


class IPUPlace(_CustomPlace):
    def __init__(self, device_id=0):
        super().__init__("ipu", device_id)


class MLUPlace(_CustomPlace):
    def __init__(self, device_id=0):
        super().__init__("mlu", device_id)



