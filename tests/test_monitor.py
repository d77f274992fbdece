"""StatRegistry monitor subsystem (reference: paddle/fluid/platform/
monitor.h StatRegistry + STAT_INT gauges; ISSUE 1 tentpole).

Covers the registry/metric API, the three exporters, the PTPU_MONITOR
gate (a disabled metric records nothing; its per-call time is bounded
in tests/test_disabled_overhead.py), the no-jax import
constraint, and the end-to-end acceptance smoke: a 2-stage pipeline +
MoE + autotune run on the CPU mesh must populate the pipeline/moe/
autotune/device series and export valid Prometheus text.
"""
import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor


@pytest.fixture(autouse=True)
def _fresh_registry():
    monitor.reset()
    monitor.enable(True)
    yield
    monitor.reset()
    monitor.refresh()


# -- registry / metric API ------------------------------------------------

def test_counter_gauge_histogram_basics():
    c = monitor.counter("t/count")
    c.inc()
    c.add(4)
    assert c.value == 5

    g = monitor.gauge("t/gauge")
    g.set(2.5)
    g.add(0.5)
    g.sub(1)
    assert g.value == 2.0

    h = monitor.histogram("t/hist")
    for v in (0.001, 0.01, 0.01, 5.0):
        h.observe(v)
    snap = monitor.snapshot()["t/hist"]
    assert snap["count"] == 4
    assert snap["min"] == 0.001 and snap["max"] == 5.0
    assert abs(snap["sum"] - 5.021) < 1e-9


def test_get_or_create_is_idempotent_and_typed():
    a = monitor.counter("t/same")
    b = monitor.counter("t/same")
    assert a is b
    with pytest.raises(TypeError):
        monitor.gauge("t/same")


def test_labeled_series():
    c = monitor.counter("t/bytes")
    c.labels(kind="all_reduce").add(100)
    c.labels(kind="all_gather").add(50)
    c.labels(kind="all_reduce").add(1)
    snap = monitor.snapshot()["t/bytes"]
    assert snap == {"kind=all_reduce": 101.0, "kind=all_gather": 50.0}


def test_callback_gauge_sampled_at_export():
    box = {"v": 1.0}
    monitor.gauge("t/live", fn=lambda: box["v"])
    assert monitor.snapshot()["t/live"] == 1.0
    box["v"] = 7.0
    assert monitor.snapshot()["t/live"] == 7.0
    # callback registration survives reset() (device gauges rely on this)
    monitor.reset()
    assert monitor.snapshot()["t/live"] == 7.0


def test_gauge_holds_lazy_device_scalar():
    import jax.numpy as jnp

    monitor.gauge("t/lazy").set(jnp.float32(3.0) * 2)
    assert monitor.snapshot()["t/lazy"] == 6.0


def test_reset_zeroes_in_place_keeping_handles():
    c = monitor.counter("t/keep")
    c.inc(3)
    monitor.reset()
    assert c.value == 0
    c.inc()   # cached handle still feeds the registry
    assert monitor.snapshot()["t/keep"] == 1.0


def test_timer_context_manager():
    with monitor.timer("t/span", phase="x"):
        time.sleep(0.01)
    snap = monitor.snapshot()["t/span"]["phase=x"]
    assert snap["count"] == 1 and snap["sum"] >= 0.009


def test_timer_disabled_registers_nothing():
    monitor.enable(False)
    try:
        with monitor.timer("t/phantom", kernel="k"):
            pass
    finally:
        monitor.enable(True)
    assert "t/phantom" not in monitor.snapshot()


def test_reset_keeps_labeled_handles_live():
    c = monitor.counter("t/labkeep").labels(kind="a")
    c.add(5)
    monitor.reset()
    c.add(2)   # cached labeled handle must still feed the registry
    assert monitor.snapshot()["t/labkeep"]["kind=a"] == 2.0


def test_export_concurrent_with_registration():
    """snapshot/export must not crash while other threads register new
    metrics and labeled series (dict-changed-during-iteration guard)."""
    stop = threading.Event()
    errors = []

    def register():
        i = 0
        while not stop.is_set():
            monitor.counter("t/conc").labels(kind=str(i % 50)).inc()
            monitor.histogram(f"t/conc_h{i % 20}").observe(i)
            i += 1

    def export():
        try:
            for _ in range(200):
                monitor.snapshot()
                monitor.export_prometheus()
        except RuntimeError as e:   # "dictionary changed size..."
            errors.append(e)

    reg = threading.Thread(target=register)
    exp = threading.Thread(target=export)
    reg.start(); exp.start()
    exp.join(); stop.set(); reg.join()
    assert not errors


def test_stat_macros_parity():
    monitor.STAT_ADD("t/stat", 5)
    monitor.STAT_SUB("t/stat", 2)
    assert monitor.snapshot()["t/stat"] == 3.0
    monitor.STAT_RESET("t/stat")
    assert monitor.snapshot()["t/stat"] == 0.0


def test_thread_safety_concurrent_increments():
    c = monitor.counter("t/mt")
    h = monitor.histogram("t/mt_h")
    N, T = 2000, 8

    def work():
        for _ in range(N):
            c.inc()
            h.observe(1.0)

    threads = [threading.Thread(target=work) for _ in range(T)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * T
    assert monitor.snapshot()["t/mt_h"]["count"] == N * T


def test_histogram_percentiles_interpolated():
    """percentile(q) interpolates inside the bucket holding the rank and
    snapshot() carries p50/p95/p99 (ISSUE 5 satellite)."""
    h = monitor.histogram("t/pct", buckets=[1.0, 2.0, 4.0, 8.0])
    for v in (0.5, 1.5, 1.5, 3.0, 3.0, 3.0, 7.0, 7.0, 7.0, 7.0):
        h.observe(v)
    snap = monitor.snapshot()["t/pct"]
    assert snap["min"] == 0.5 and snap["max"] == 7.0
    assert snap["min"] <= snap["p50"] <= snap["p95"] <= snap["p99"] \
        <= snap["max"]
    # rank 5 of 10 falls in the (2, 4] bucket (3 of its obs) → inside it
    assert 2.0 <= snap["p50"] <= 4.0
    # p99 (rank 9.9) is in the last occupied bucket, clamped by max
    assert 4.0 <= snap["p99"] <= 7.0
    assert h.percentile(50) == snap["p50"]
    assert h.percentile(0) == 0.5            # clamps to observed min
    assert h.percentile(100) == 7.0          # ... and max
    assert monitor.histogram("t/pct_empty").percentile(95) == 0.0


def test_histogram_percentile_single_bucket_stays_in_range():
    h = monitor.histogram("t/pct1")
    for _ in range(100):
        h.observe(0.0123)
    for q in (1, 50, 99):
        assert h.percentile(q) == pytest.approx(0.0123)


def test_percentiles_reach_profiler_summary():
    from paddle_tpu import profiler

    monitor.histogram("t/summ").observe(0.25)
    with profiler.Profiler(timer_only=True) as prof:
        prof.step()
    text = prof.summary()
    assert "t/summ" in text and "p50=" in text and "p95=" in text


def test_gauge_callback_error_keeps_exporting():
    """Regression (ISSUE 5 satellite): an exception inside a callback
    gauge during snapshot/render must not take down the exporter — it is
    counted in monitor/gauge_errors{name} and rendering continues."""
    monitor.gauge("t/boom", fn=lambda: 1 / 0)
    monitor.counter("t/alive").inc()

    snap = monitor.snapshot()                 # must not raise
    assert snap["t/boom"] == 0.0 and snap["t/alive"] == 1.0
    text = monitor.export_prometheus()        # must not raise either
    assert "t_alive 1" in text and "t_boom 0" in text
    assert "t/alive" in monitor.render()      # render survives too
    # the failure is visible, per failing gauge, and accumulates
    errs = monitor.snapshot()["monitor/gauge_errors"]
    assert errs["name=t/boom"] >= 2.0         # snapshot + prometheus
    # a healthy callback gauge next to it still samples live
    box = {"v": 5.0}
    monitor.gauge("t/fine", fn=lambda: box["v"])
    assert monitor.snapshot()["t/fine"] == 5.0


# -- exporters ------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"                      # metric name
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\""            # first label
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"]*\")*\})?"       # more labels
    r" -?[0-9.eE+-]+|[+-]Inf|NaN$")


def test_export_prometheus_parses():
    monitor.counter("pipe/bytes").labels(kind="all_reduce").add(1024)
    monitor.gauge("pipe/bubble").set(0.25)
    monitor.histogram("pipe/lat").observe(0.002)
    text = monitor.export_prometheus()
    assert '# TYPE pipe_bytes counter' in text
    assert '# TYPE pipe_bubble gauge' in text
    assert '# TYPE pipe_lat histogram' in text
    seen_inf = False
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]*", line)
            continue
        assert _PROM_LINE.match(line), f"unparseable line: {line!r}"
        if '_bucket{' in line and 'le="+Inf"' in line:
            seen_inf = True
    assert seen_inf, "histogram must export a +Inf bucket"
    # cumulative buckets: +Inf count equals _count
    m = re.search(r'pipe_lat_bucket\{le="\+Inf"\} (\d+)', text)
    n = re.search(r"pipe_lat_count (\d+)", text)
    assert m.group(1) == n.group(1) == "1"


def test_export_jsonl_appends_time_series(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    monitor.counter("t/j").inc()
    monitor.export_jsonl(path)
    monitor.counter("t/j").inc()
    monitor.export_jsonl(path)
    lines = [json.loads(l) for l in open(path)]
    assert len(lines) == 2
    assert lines[0]["metrics"]["t/j"] == 1.0
    assert lines[1]["metrics"]["t/j"] == 2.0
    assert lines[1]["ts"] >= lines[0]["ts"]


# -- env gate + overhead guard (ISSUE 1 satellite: CI/tooling) ------------

def test_env_gate_refresh(monkeypatch):
    monkeypatch.setenv("PTPU_MONITOR", "0")
    monitor.refresh()
    c = monitor.counter("t/gated")
    c.inc()
    assert c.value == 0 and monitor.enabled() is False
    monkeypatch.setenv("PTPU_MONITOR", "1")
    monitor.refresh()
    c.inc()
    assert c.value == 1


def test_disabled_overhead_guard():
    """A disabled counter increment returns at the gate: it takes no
    lock and leaves the counter's state as it was, so PTPU_MONITOR=0
    can never regress the hot path.  The per-call time bound lives in
    tests/test_disabled_overhead.py (slow tier)."""
    monitor.enable(False)
    try:
        c = monitor.counter("t/overhead")
        before = (c.value, c._touched, dict(c._children))
        assert c.inc() is c
        assert (c.value, c._touched, dict(c._children)) == before
    finally:
        monitor.enable(True)
    assert c.value == 0
    assert "t/overhead" not in monitor.snapshot()


def test_monitor_imports_without_jax():
    """The monitor module is stdlib-only: loading it standalone must not
    pull jax (so telemetry tooling never triggers device init)."""
    mod_path = os.path.join(
        os.path.dirname(monitor.__file__), "__init__.py")
    code = (
        "import sys, importlib.util\n"
        f"spec = importlib.util.spec_from_file_location('mon_alone', {mod_path!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "assert 'jax' not in sys.modules, 'monitor must not import jax'\n"
        "m.counter('x').inc(2)\n"
        "assert m.snapshot()['x'] == 2\n"
        "assert 'x 2' in m.export_prometheus()\n"
    )
    subprocess.run([sys.executable, "-c", code], check=True, timeout=60)


# -- hot-path wiring ------------------------------------------------------

def test_optimizer_step_series():
    from paddle_tpu import nn, optimizer

    model = nn.Linear(8, 4)
    opt = optimizer.AdamW(learning_rate=1e-3, parameters=model.parameters())
    x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
    for _ in range(2):
        loss = (model(x) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
    snap = monitor.snapshot()
    assert snap["optimizer/steps"] == 2.0
    assert snap["optimizer/lr"] == pytest.approx(1e-3)
    assert snap["optimizer/grad_norm"] > 0.0


def test_collective_bytes_series():
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import parallel
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.distributed import collective as coll
    from paddle_tpu.parallel import mesh as mesh_mod

    prev_mesh = mesh_mod._current()
    try:
        mesh = parallel.init_mesh(dp=2)
        group = coll.new_group(axis_name="dp")

        @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
                           out_specs=P("dp"), axis_names=frozenset({"dp"}),
                           check_vma=False)
        def body(a):
            return coll.all_reduce(Tensor(a), group=group)._data

        jax.jit(body)(jnp.ones((2, 8), jnp.float32))
    finally:
        mesh_mod._state.mesh = prev_mesh
    snap = monitor.snapshot()
    # counted at trace time from the per-shard aval: [1, 8] f32
    assert snap["collective/bytes"]["kind=all_reduce"] == 1 * 8 * 4
    assert snap["collective/calls"]["kind=all_reduce"] == 1.0


def test_end_to_end_acceptance_smoke():
    """ISSUE 1 acceptance: after a 2-stage pipeline + MoE + autotune smoke
    run on CPU, snapshot() has non-zero pipeline/stage_time,
    moe/tokens_per_expert, autotune/hits+misses and device/peak_bytes, and
    export_prometheus() output parses."""
    import jax.numpy as jnp

    from paddle_tpu import parallel
    from paddle_tpu.parallel import mesh as mesh_mod
    from paddle_tpu.parallel.moe import moe_mlp_arrays
    from paddle_tpu.parallel.pipeline import pipeline_apply
    from paddle_tpu.ops import autotune as at

    prev_mesh = mesh_mod._current()
    try:
        parallel.init_mesh(pp=2)
        rng = np.random.RandomState(0)
        L, H, B = 4, 8, 4
        params = {"w": jnp.asarray(rng.randn(L, H, H), jnp.float32) * 0.3}
        x = jnp.asarray(rng.randn(B, H), jnp.float32)
        out = pipeline_apply(
            lambda p, h: jnp.tanh(h @ p["w"]), params, x, n_microbatches=2)
        assert out.shape == (B, H)

        xm = jnp.asarray(rng.randn(2, 8, 16).astype(np.float32))
        gl = jnp.asarray(rng.randn(2, 8, 4).astype(np.float32))
        wi = jnp.asarray(rng.randn(4, 16, 32).astype(np.float32) * 0.05)
        wo = jnp.asarray(rng.randn(4, 32, 16).astype(np.float32) * 0.05)
        moe_mlp_arrays(xm, gl, wi, wo)

        at.cache.clear()
        at.autotune("smoke", (1,), [(1,), (2,)])
        at.autotune("smoke", (1,), [(1,), (2,)])
    finally:
        mesh_mod._state.mesh = prev_mesh

    snap = monitor.snapshot()
    assert snap["pipeline/stage_time"]["schedule=gpipe"]["count"] > 0
    assert snap["pipeline/stage_time"]["schedule=gpipe"]["sum"] > 0
    assert snap["pipeline/bubble_fraction"]["schedule=gpipe"] == \
        pytest.approx(1 / 3)
    assert snap["moe/tokens_per_expert"]["count"] == 4   # one obs per expert
    assert snap["moe/tokens_per_expert"]["sum"] > 0
    assert snap["autotune/hits"] == 1.0
    assert snap["autotune/misses"] == 1.0
    assert snap["device/peak_bytes"] > 0
    for line in monitor.export_prometheus().strip().splitlines():
        assert line.startswith("#") or _PROM_LINE.match(line), line

    # the same names flow into Profiler.summary()'s monitor section
    from paddle_tpu import profiler

    with profiler.Profiler(timer_only=True) as prof:
        prof.step()
    text = prof.summary()
    assert "runtime monitor" in text
    assert "pipeline/stage_time" in text
