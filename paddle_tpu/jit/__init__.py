"""Whole-graph compilation (reference: paddle.jit.to_static,
python/paddle/jit/api.py:222 + dy2static/program_translator.py).

TPU-native re-design: the reference needs ~20 AST transformers to lift
dygraph python into a ProgramDesc. Here the eager engine itself is
jax-traceable — ops dispatch to pure jax functions, autograd records vjp
closures, the optimizer update is a pure pytree function — so "to static"
is simply: run the SAME eager python under a jax trace with all framework
state (params, buffers, optimizer slots, RNG key, lr) lifted to function
inputs/outputs. One XLA program per (input shapes) — the analog of the
reference's PartialProgramLayer + InterpreterCore, with buffer donation
standing in for its memory-reuse passes.
"""
from __future__ import annotations

import contextlib
import functools
import re
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core import random as _rng
from ..autograd import tape
from ..nn.layer import Layer
from .. import monitor
from ..monitor import trace as mtrace
from ..monitor import perf as mperf

_NULL_CTX = contextlib.nullcontext()


def _arg_signature(tree) -> str:
    """Compact shape/dtype signature of a call's DATA arguments — the
    part of jax.jit's cache key the caller controls.  A signature this
    CompiledFunction has not seen before means jax is about to trace and
    XLA-compile a fresh program; that event gets a `jit/recompile` span
    carrying the missing signature plus a `jit/recompiles{fn}` count
    (today's answer to "why did step 1047 take 90 seconds")."""
    parts = []

    def walk(o):
        if isinstance(o, (list, tuple)):
            for x in o:
                walk(x)
        elif isinstance(o, dict):
            for k in sorted(o):
                walk(o[k])
        else:
            shape = getattr(o, "shape", None)
            if shape is not None:
                parts.append(f"{tuple(shape)}:{getattr(o, 'dtype', '?')}")
            else:   # static python leaf: value participates in the key
                parts.append(repr(o)[:48])

    walk(tree)
    return ";".join(parts)


_SIG_PART = re.compile(r"^\(([^)]*)\):(\S+)$")


def _signature_delta(cached_sigs, new_sig):
    """Name the axis that varies between `new_sig` and the CLOSEST
    cached signature — the recompile explainer (ISSUE 12): a
    `jit/recompiles` miss becomes "dim1 32→64" instead of a mystery.

    Returns ``(axis, detail)`` or None when there is nothing to diff.
    Axes: ``dim<i>`` (one shape dimension changed), ``shape`` (rank or
    several dims), ``dtype``, ``static`` (a python-leaf value), and
    ``nargs`` (the flattened argument count itself changed).  Part
    indices are positions in the flattened (args, kwargs) tree."""
    if not cached_sigs:
        return None
    new_parts = new_sig.split(";")

    def score(old):
        ps = old.split(";")
        if len(ps) != len(new_parts):
            return -1
        return sum(a == b for a, b in zip(ps, new_parts))

    # sorted(): cached_sigs is a set — tie-breaks must not depend on
    # hash order (ptpu-check[determinism] would rightly flag raw iteration)
    best = max(sorted(cached_sigs), key=score)
    old_parts = best.split(";")
    if len(old_parts) != len(new_parts):
        return ("nargs",
                f"{len(old_parts) - 1}→{len(new_parts) - 1} args")
    for i, (a, b) in enumerate(zip(old_parts, new_parts)):
        if a == b:
            continue
        if i == 0:                      # the "nstate=K" prefix itself
            return "state", f"{a}→{b}"
        ma, mb = _SIG_PART.match(a), _SIG_PART.match(b)
        if ma is None or mb is None:
            return "static", f"arg{i - 1}: {a}→{b}"
        if ma.group(2) != mb.group(2):
            return ("dtype",
                    f"arg{i - 1}: {ma.group(2)}→{mb.group(2)}")
        da = [d for d in ma.group(1).replace(" ", "").split(",") if d]
        db = [d for d in mb.group(1).replace(" ", "").split(",") if d]
        if len(da) != len(db):
            return ("shape",
                    f"arg{i - 1}: ({ma.group(1)})→({mb.group(1)})")
        diffs = [j for j, (x, y) in enumerate(zip(da, db)) if x != y]
        if len(diffs) == 1:
            j = diffs[0]
            return f"dim{j}", f"arg{i - 1} dim{j}: {da[j]}→{db[j]}"
        return ("shape",
                f"arg{i - 1}: ({ma.group(1)})→({mb.group(1)})")
    return None


__all__ = ["to_static", "compile", "CompiledFunction", "save", "load", "TranslatedLayer", "not_to_static", "ignore_module"]


def _collect_layers(args) -> List[Layer]:
    out = []
    for a in args:
        if isinstance(a, Layer):
            out.append(a)
    return out


class _StateSpec:
    """All mutable framework state a compiled program threads through
    (the analog of the reference Program's persistable vars)."""

    # (scaler attr name, threaded dtype) — the GradScaler state that the
    # in-graph dynamic-loss-scaling protocol updates through the step
    SCALER_ATTRS = (("_scale", jnp.float32), ("_good_steps", jnp.int32),
                    ("_bad_steps", jnp.int32))

    def __init__(self, models=(), optimizers=(), scalers=()):
        self.models = list(models)
        self.optimizers = list(optimizers)
        self.scalers = list(scalers)

    def slots(self):
        """list of (name, get_fn, set_fn) for every mutable array slot."""
        out = []
        for mi, m in enumerate(self.models):
            for name, p in m.named_parameters():
                out.append((f"m{mi}.{name}", p))
            for name, b in m.named_buffers():
                out.append((f"m{mi}.buf.{name}", b))
        for oi, opt in enumerate(self.optimizers):
            # Ensure slot accumulators exist before tracing (concrete zeros).
            for p in opt._parameter_list:
                opt._ensure_state(p)
            for key, slot_dict in opt._states.items():
                # sorted: slot dicts may be REBUILT by meta-optimizers
                # (GradientMerge's select replaces the dict each step), so
                # insertion order is not stable between trace time and
                # later calls — a canonical order keeps the threaded
                # positions fixed no matter how the dict was assembled
                for sname in sorted(slot_dict):
                    out.append((f"o{oi}.{key}.{sname}", (opt, key, sname)))
            for key in opt._master_weights:
                out.append((f"o{oi}.{key}.master", (opt, key, "__master__")))
        for si, sc in enumerate(self.scalers):
            for attr, _ in self.SCALER_ATTRS:
                out.append((f"sc{si}.{attr}", (sc, attr, "__scaler__")))
        return out

    def read(self):
        vals = []
        for name, slot in self.slots():
            if isinstance(slot, Tensor):
                vals.append(slot._data)
            else:
                opt, key, sname = slot
                if sname == "__scaler__":
                    dt = dict(self.SCALER_ATTRS)[key]
                    vals.append(jnp.asarray(getattr(opt, key), dt))
                elif sname == "__master__":
                    vals.append(opt._master_weights[key])
                else:
                    vals.append(opt._states[key][sname])
        return vals

    def write(self, vals):
        for (name, slot), v in zip(self.slots(), vals):
            if isinstance(slot, Tensor):
                slot._data = v
            else:
                opt, key, sname = slot
                if sname == "__scaler__":
                    setattr(opt, key, v)
                elif sname == "__master__":
                    opt._master_weights[key] = v
                else:
                    opt._states[key][sname] = v


def _tree_to_arrays(obj):
    if isinstance(obj, Tensor):
        return obj._data
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_to_arrays(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_to_arrays(v) for k, v in obj.items()}
    from .dy2static.convert_operators import _Undefined

    if isinstance(obj, _Undefined):
        # a name that converted control flow left possibly-unbound is
        # being RETURNED — surface its actionable error instead of a
        # jax invalid-output-type failure
        obj._raise()
    return obj


def _tree_to_tensors(obj):
    if isinstance(obj, (jnp.ndarray, jax.Array)) or hasattr(obj, "aval"):
        return Tensor(obj)
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_to_tensors(o) for o in obj)
    if isinstance(obj, dict):
        return {k: _tree_to_tensors(v) for k, v in obj.items()}
    return obj


def _wrap_inputs(obj):
    """arrays → Tensors for feeding the python fn during trace."""
    return _tree_to_tensors(obj)


class CompiledFunction:
    """A compiled (and state-threading) callable.

    in_shardings/out state handling:
      state_in  = current framework state arrays (donated)
      host_in   = per-call host scalars (lr, step) per optimizer
      key       = RNG key (split per call)
    """

    def __init__(self, fn, models=(), optimizers=(), donate=True,
                 train=True, sharding_fn=None, static_argnums=(),
                 scalers=()):
        self._fn = fn
        self._spec = _StateSpec(models, optimizers, scalers)
        self._donate = donate
        self._train = train
        self._sharding_fn = sharding_fn
        self._compiled = None
        self._last_lowered = None
        self._seen_sigs: set = set()
        # per-signature AOT executables + captured XLA analyses: the perf
        # hook routes fresh compiles through jax's AOT path (ONE compile,
        # analyses read off the same executable), and memory_analysis()
        # answers repeat calls from here instead of re-lowering
        self._aot_cache: Dict[str, Any] = {}
        self._analysis_cache: Dict[str, Dict[str, Any]] = {}
        # sig -> perf-record label: a new input signature is a DIFFERENT
        # compiled program, and perf.capture routes it to its own
        # `name#N` record so its wall times never dilute another
        # program's MFU — observe() must use the same routed label
        self._perf_labels: Dict[str, str] = {}

    def _build(self):
        spec = self._spec
        fn = self._fn
        import os as _os

        if _to_static_enabled and _os.environ.get("PTPU_DY2STATIC", "1") != "0":
            # dy2static: rewrite python if/while/for over tensor values
            # into staged control flow (no-op for functions without any,
            # and python-valued predicates keep python semantics)
            from .dy2static import convert_to_static

            fn = convert_to_static(fn)
        train = self._train

        def pure(state_vals, host_vals, key, args, kwargs):
            spec_slots_backup = spec.read()
            overrides = []
            try:
                spec.write(state_vals)
                for oi, opt in enumerate(spec.optimizers):
                    opt._lr_override = host_vals[2 * oi]
                    opt._step_override = host_vals[2 * oi + 1]
                    overrides.append(opt)
                for sc in spec.scalers:
                    sc._in_compiled_step = True
                with _rng.key_scope(key):
                    with tape.enable_grad() if train else tape.no_grad():
                        t_args = _wrap_inputs(args)
                        t_kwargs = _wrap_inputs(kwargs)
                        out = fn(*t_args, **t_kwargs)
                new_state = spec.read()
                out_arrays = _tree_to_arrays(out)
                return out_arrays, new_state
            finally:
                for opt in overrides:
                    opt._lr_override = None
                    opt._step_override = None
                for sc in spec.scalers:
                    sc._in_compiled_step = False
                    sc._found_inf = False  # never leak a tracer past trace
                    sc._unscaled = False
                spec.write(spec_slots_backup)

        donate = (0,) if self._donate else ()
        self._compiled = jax.jit(pure, donate_argnums=donate)

    def __call__(self, *args, **kwargs):
        if self._compiled is None:
            self._build()
        state_vals = self._spec.read()
        host_vals = []
        for opt in self._spec.optimizers:
            opt._step_count += 1
            host_vals.append(jnp.asarray(opt.get_lr(), jnp.float32))
            host_vals.append(jnp.asarray(opt._step_count, jnp.int32))
        key = _rng.next_key()
        a_args = _tree_to_arrays(args)
        a_kwargs = _tree_to_arrays(kwargs)
        # recompile visibility: a data-arg signature this function has
        # not run before means jax.jit is about to trace+compile — time
        # it as a span and count it, instead of it showing up as one
        # mysteriously slow step.  (State arrays keep their shapes across
        # steps, so the caller-visible args are the discriminating part;
        # signature cost is a few string formats per call, skipped
        # entirely when both telemetry layers are off.)
        ctx = _NULL_CTX
        perf_on = mperf.enabled()
        exec_fn = self._compiled
        if monitor.enabled() or mtrace.enabled() or perf_on:
            sig = f"nstate={len(state_vals)};{_arg_signature((a_args, a_kwargs))}"
            if sig not in self._seen_sigs:
                # recompile explainer (ISSUE 12): BEFORE recording the
                # fresh signature, diff it against the cached ones and
                # name the varying axis — a compile storm's post-mortem
                # then reads "seq_len grew every step", not 40 opaque
                # signature strings
                cause = _signature_delta(self._seen_sigs, sig)
                self._seen_sigs.add(sig)
                fname = getattr(self._fn, "__name__", "<step>")
                monitor.counter(
                    "jit/recompiles",
                    "fresh trace+XLA-compile events per function").labels(
                    fn=fname).inc()
                span_attrs = {"fn": fname, "signature": sig}
                if cause is not None:
                    axis, detail = cause
                    monitor.counter(
                        "jit/recompile_cause",
                        "recompiles by the signature axis that varied"
                    ).labels(fn=fname, axis=axis).inc()
                    monitor.flight.note("jit/recompile", fn=fname,
                                        axis=axis, detail=detail)
                    span_attrs["cause"] = detail
                ctx = mtrace.span("jit/recompile", **span_attrs)
        t0 = 0.0
        with ctx:
            if perf_on:
                # perf accounting: dispatch through the per-signature AOT
                # executable so XLA's cost/memory analyses come off the
                # ONE compile this signature pays anyway — inside the
                # recompile span, which exists to surface exactly this
                # compile cost
                exec_fn = self._aot_exec(
                    sig, (state_vals, host_vals, key, a_args, a_kwargs))
                t0 = time.perf_counter()
            out_arrays, new_state = exec_fn(
                state_vals, host_vals, key, a_args, a_kwargs)
        if perf_on:
            # perf mode is explicitly a synced diagnostic mode: MFU from
            # an async dispatch time would be fiction
            jax.block_until_ready((out_arrays, new_state))
            mperf.observe(self._perf_labels.get(sig, self._perf_label()),
                          time.perf_counter() - t0)
        if self._spec.optimizers and monitor.enabled():
            # the compiled program embeds the optimizer update; count the
            # dispatch here (optimizer.step only counts eager steps).
            # host_vals[0] is this step's lr, already computed above —
            # stored lazily, coerced at monitor export.
            monitor.counter("optimizer/steps").inc(len(self._spec.optimizers))
            monitor.gauge("optimizer/lr").set(host_vals[0])
        self._spec.write(new_state)
        # clear stale grads: the compiled step owns the whole update
        for opt in self._spec.optimizers:
            for p in opt._parameter_list:
                p.grad = None
        return _tree_to_tensors(out_arrays)

    # -- introspection/AOT -------------------------------------------------
    def _perf_label(self) -> str:
        return getattr(self._fn, "__name__", "<step>")

    def _aot_exec(self, sig, vals):
        """The AOT executable for `sig`, compiling (and feeding the perf
        registry XLA's cost/memory analyses) on first sight.  Any AOT
        failure falls back to the normal jax.jit dispatch path — counted,
        so perf mode can never make a previously-working step uncallable.
        """
        exec_fn = self._aot_cache.get(sig)
        if exec_fn is None:
            try:
                lowered = self._compiled.lower(*vals)
                exec_fn = lowered.compile()
                rec = mperf.capture(self._perf_label(), lowered=lowered,
                                    compiled=exec_fn)
                self._perf_labels[sig] = rec.label
                if rec.memory:
                    # only a real analysis pre-fills the cache — a failed
                    # probe must not serve another signature's bytes to a
                    # memfit gate
                    self._analysis_cache[sig] = dict(rec.memory)
            except Exception:   # ptpu-check[silent-except]: AOT lowering support varies
                # (exotic shardings/backends); dispatch path still works
                monitor.counter(
                    "perf/aot_fallbacks",
                    "perf-mode AOT compiles that fell back to dispatch"
                ).labels(fn=self._perf_label()).inc()
                exec_fn = self._compiled
                # a fallback sig has NO captured analysis: its wall times
                # must land in their own analysis-less record, never the
                # base record whose flops belong to a different program
                self._perf_labels[sig] = f"{self._perf_label()}#fallback"
            self._aot_cache[sig] = exec_fn
        return exec_fn

    def memory_analysis(self, *args, **kwargs):
        """XLA's compile-time memory analysis for this step at the given
        example inputs: dict with argument/output/temp/alias bytes and
        the derived peak live estimate. Chip-free (works on the CPU test
        mesh) — the per-device HBM complement to
        device.max_memory_allocated()'s runtime peak.

        Cached per input signature (and pre-filled by the perf hook's
        capture), so repeated calls — a memfit gate polling every few
        steps, say — pay the lower+compile exactly once."""
        if self._compiled is None:
            self._build()
        a_args = _tree_to_arrays(args)
        a_kwargs = _tree_to_arrays(kwargs)
        sig = (f"nstate={len(self._spec.slots())};"
               f"{_arg_signature((a_args, a_kwargs))}")
        cached = self._analysis_cache.get(sig)
        if cached is not None:
            return dict(cached)
        mem = self.lower(*args, **kwargs).compile().memory_analysis()
        out = {k: int(getattr(mem, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes") if hasattr(mem, k)}
        out["peak_bytes_estimate"] = (
            out.get("argument_size_in_bytes", 0)
            + out.get("temp_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0))
        self._analysis_cache[sig] = out
        return dict(out)

    def lower(self, *args, **kwargs):
        if self._compiled is None:
            self._build()
        state_vals = self._spec.read()
        host_vals = []
        for opt in self._spec.optimizers:
            host_vals.append(jnp.asarray(opt.get_lr(), jnp.float32))
            host_vals.append(jnp.asarray(opt._step_count, jnp.int32))
        key = _rng.get_state()
        return self._compiled.lower(
            state_vals, host_vals, key, _tree_to_arrays(args), _tree_to_arrays(kwargs)
        )


def compile(fn=None, models=(), optimizers=(), donate=True, train=True,
            scalers=()):
    """Compile a whole train/eval step. The blessed TPU path:

        step = paddle_tpu.jit.compile(train_step, models=[model], optimizers=[opt])
        loss = step(x, y)          # ONE XLA program: fwd+bwd+optimizer

    A GradScaler used inside the step (dynamic fp16 loss scaling) must be
    registered via scalers=[scaler] so its scale/counters thread through
    the compiled program (in-graph check_finite_and_unscale semantics).
    """
    if fn is None:
        return functools.partial(compile, models=models, optimizers=optimizers,
                                 donate=donate, train=train, scalers=scalers)
    if isinstance(models, Layer):
        models = [models]
    return CompiledFunction(fn, models, optimizers, donate, train,
                            scalers=scalers)


class StaticFunction:
    """to_static-wrapped Layer.forward (inference/forward-only compile;
    caches one executable per input signature like the reference's
    StaticFunction per-input-spec cache)."""

    def __init__(self, layer_or_fn, input_spec=None):
        if isinstance(layer_or_fn, Layer):
            self._layer = layer_or_fn
            self._fn = layer_or_fn.forward
        else:
            self._layer = None
            self._fn = layer_or_fn
        self._input_spec = input_spec
        self._compiled = None

    def _models(self):
        return [self._layer] if self._layer is not None else []

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:
            # ProgramTranslator.enable(False) parity: run the original
            # eager python (debuggable path)
            return self._fn(*args, **kwargs)
        if self._compiled is None:
            self._compiled = CompiledFunction(
                self._fn, models=self._models(), optimizers=(),
                donate=False, train=False,
            )
        return self._compiled(*args, **kwargs)


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """Decorator/wrapper: compile a Layer's forward (or a function) into one
    XLA program. For full train-step compilation (fwd+bwd+opt) use
    paddle_tpu.jit.compile."""

    def decorate(obj):
        if isinstance(obj, Layer):
            st = StaticFunction(obj, input_spec)
            obj._static_forward = st
            obj.forward_original = obj.forward
            # route __call__ through the compiled path
            obj.forward = lambda *a, **kw: st(*a, **kw)
            return obj
        return StaticFunction(obj, input_spec)

    if function is None:
        return decorate
    return decorate(function)


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


# ---------------------------------------------------------------------------
# AOT save/load (reference: jit.save → TranslatedLayer + AnalysisPredictor)
# ---------------------------------------------------------------------------

def save(layer, path, input_spec=None, **config):
    """Serialize a Layer's forward as a portable XLA AOT artifact
    (jax.export StableHLO bytes) + weights. Reference analog:
    paddle.jit.save producing model+pdiparams loadable by inference
    (SURVEY §3.6)."""
    import pickle
    from jax import export as jax_export

    if input_spec is None:
        raise ValueError("input_spec (example Tensors or ShapeDtype tuples) required")
    example = []
    for s in input_spec:
        if isinstance(s, Tensor):
            example.append(jax.ShapeDtypeStruct(s.shape, s.dtype))
        elif isinstance(s, (tuple, list)):
            shape, dtype = s
            example.append(jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype)))
        else:
            example.append(s)

    params, bufs = layer.state_arrays()
    layer.eval()

    def fwd(params, bufs, *xs):
        backup_p, backup_b = layer.state_arrays()
        try:
            layer.load_state_arrays(params, bufs)
            with tape.no_grad():
                out = layer(*[Tensor(x) for x in xs])
            return _tree_to_arrays(out)
        finally:
            layer.load_state_arrays(backup_p, backup_b)

    jitted = jax.jit(fwd)
    exported = jax_export.export(jitted)(params, bufs, *example)
    blob = {
        "stablehlo": exported.serialize(),
        "params": {k: np.asarray(v) for k, v in params.items()},
        "buffers": {k: np.asarray(v) for k, v in bufs.items()},
    }
    with open(path + ".ptpu" if not path.endswith(".ptpu") else path, "wb") as f:
        pickle.dump(blob, f, protocol=4)


class TranslatedLayer(Layer):
    """Deserialized AOT program (reference: jit/translated_layer.py)."""

    def __init__(self, exported, params, buffers):
        super().__init__()
        self._exported = exported
        self._params_np = params
        self._buffers_np = buffers

    def forward(self, *xs):
        arrs = [x._data if isinstance(x, Tensor) else jnp.asarray(x) for x in xs]
        params = {k: jnp.asarray(v) for k, v in self._params_np.items()}
        bufs = {k: jnp.asarray(v) for k, v in self._buffers_np.items()}
        out = self._exported.call(params, bufs, *arrs)
        return _tree_to_tensors(out)


def load(path, **config):
    import pickle
    from jax import export as jax_export

    fname = path + ".ptpu" if not path.endswith(".ptpu") else path
    with open(fname, "rb") as f:
        blob = pickle.load(f)
    exported = jax_export.deserialize(blob["stablehlo"])
    return TranslatedLayer(exported, blob["params"], blob["buffers"])


def enable_to_static(enable=True):
    """Globally toggle @to_static conversion (reference
    ProgramTranslator.enable). When disabled, to_static-wrapped callables
    run their original eager python."""
    global _to_static_enabled
    _to_static_enabled = bool(enable)


_to_static_enabled = True


def set_code_level(level=100, also_to_stdout=False):
    """Dy2static transformed-code logging level (reference
    jit/set_code_level). This engine traces the eager tape instead of
    rewriting AST — there is no transformed code to print; the level is
    recorded for API parity."""
    global _code_level
    _code_level = level


def set_verbosity(level=0, also_to_stdout=False):
    global _verbosity
    _verbosity = level


_code_level = 0
_verbosity = 0

__all__ += ["enable_to_static", "set_code_level", "set_verbosity"]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache somewhere that survives
    the process, and return the directory in effect.  Entry points
    (chip_smoke.py, benchmark/run.py) call this before their first trace;
    `import paddle_tpu` does not.

    ``JAX_COMPILATION_CACHE_DIR`` set: nothing is set here — JAX reads that
    variable itself, and whoever set it owns the location.  Unset: the
    cache goes to ``<checkout>/.jax_cache``, a FIXED path (the path is
    part of the cache key, so a tempdir or a per-run name never hits)."""
    import os

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ += ["enable_compile_cache"]

# Staged lists: value-semantics fixed-capacity lists for code that appends
# under converted (tensor-dependent) control flow — see
# dy2static/staged_array.py (reference convert_operators.py:117
# maybe_to_tensor_array).
from .dy2static import StagedArray, staged_list  # noqa: E402

__all__ += ["StagedArray", "staged_list"]
