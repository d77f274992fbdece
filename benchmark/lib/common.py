"""What every kind of runner needs: the model and its seeded weights, the
compile counter, the device report and the profiler slice."""
import contextlib
import glob
import importlib
import os
import shutil
import tempfile
import time


def log(msg):
    import sys

    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def fold_seed(seed):
    """--seed may exceed 32 signed bits; every generator here takes this."""
    return int(seed) % (2 ** 31 - 1)


class Laps:
    """Splits set-up into named parts: `laps(name)` books the time since
    the last call (or since construction) under `name`."""

    def __init__(self, t0):
        self._mark = time.perf_counter()
        self.split = {"imports": self._mark - t0}

    def __call__(self, name):
        now = time.perf_counter()
        self.split[name] = now - self._mark
        self._mark = now

    def __str__(self):
        return ", ".join(f"{k} {v:.1f}" for k, v in self.split.items())


class CompileCounter:
    """Backend compiles and persistent-cache traffic of this process, as
    `jax.monitoring` reports them."""

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
                "compile_s": round(self.compile_s, 2),
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def resolve(path):
    """'package.module:attribute' -> the attribute."""
    module, _, attr = path.partition(":")
    return getattr(importlib.import_module(module), attr)


def model_config(config):
    """The program's configuration object, from a configuration file: the
    constructor named under `harness`, called with every number at the
    file's top level (the model's sizes) and the harness's own kwargs."""
    sizes = {k: v for k, v in config.items()
             if isinstance(v, (int, float)) and not isinstance(v, bool)}
    harness = config["harness"]
    return resolve(harness["constructor"])(**sizes, **harness["kwargs"])


def _weight_rule(name):
    leaf = name.rsplit(".", 1)[-1]
    if leaf.endswith("_b") or leaf == "bias":
        return "zeros"
    if leaf.startswith("ln") or ".ln_f." in name:
        return "ones"
    return "normal"


def build_model(config, seed):
    """`GPTForCausalLM` of a configuration file with weights from `seed`.

    The model object is built under `LazyGuard` on the host (placeholders,
    no device memory), then every weight is made on the default device in
    ONE jitted call, in the type it is served or trained in: matrices and
    embeddings N(0, initializer_range), LayerNorm scales 1, biases 0 - the
    program's own initial distribution.  Returns (model, cfg)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.compat import LazyGuard
    from paddle_tpu.models import GPTForCausalLM

    cfg = model_config(config)
    dtype = jnp.dtype(config["harness"]["dtype"])
    with jax.default_device(jax.devices("cpu")[0]), LazyGuard():
        model = GPTForCausalLM(cfg)
    named = list(model.named_parameters())
    rules = [_weight_rule(n) for n, _ in named]
    shapes = [tuple(p.shape) for _, p in named]
    std = float(cfg.initializer_range)

    def fill(key):
        out = []
        for i, (rule, shape) in enumerate(zip(rules, shapes)):
            if rule == "normal":
                # a stacked [L, ...] weight one layer at a time, so the
                # float32 draw never exceeds one layer's size
                keys = jax.random.split(jax.random.fold_in(key, i), shape[0])
                out.append(jax.lax.map(
                    lambda k, rest=shape[1:]: (jax.random.normal(
                        k, rest, jnp.float32) * std).astype(dtype), keys))
            else:
                out.append(jnp.full(shape, rule == "ones", dtype))
        return out

    weights = jax.jit(fill)(jax.random.PRNGKey(fold_seed(seed)))
    for (_, p), w in zip(named, weights):
        p._data = w
        p._lazy_init = None
    model.to(dtype=config["harness"]["dtype"])     # no copy: sets the layers' dtype
    return model, cfg


def device_info():
    import jax

    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}          # None on the CPU backend
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


@contextlib.contextmanager
def profiler_slice(result):
    """Trace what runs inside the block with JAX's profiler into a
    directory under TMPDIR; on exit `result["xplane"]` is the path of the
    .xplane.pb and `result["cleanup"]()` removes the directory."""
    import jax

    tmp = tempfile.mkdtemp(prefix="bench-trace-")
    result["cleanup"] = lambda: shutil.rmtree(tmp, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # device ops and bench: spans only
    jax.profiler.start_trace(tmp, profiler_options=options)
    t0 = time.perf_counter()
    try:
        yield
    finally:
        result["host_slice_s"] = time.perf_counter() - t0
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        result["xplane"] = found[0] if found else None
