"""paddle_tpu.monitor v2 — span tracing, flight recorder, watchdog, live
endpoint (ISSUE 5 tentpole).

The bar: a disabled span costs < 1 µs (mirroring the PR-1 metric guard);
context propagates across threads; a traced serving request decomposes
into queue-wait → prefill → per-step decode spans whose durations sum to
(approximately) the request's wall time, with `serving/ttft` and
`serving/tpot` histograms populated; a SIGTERM'd subprocess leaves a
parseable flight-recorder dump holding its last spans; a
PTPU_FAULTS-injected stall triggers the watchdog dump with all-thread
py-stacks; and `/metrics` //healthz //traces serve live state.
"""
import json
import os
import pathlib
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import flight, trace
from paddle_tpu.resilience import faults

_WORKER = pathlib.Path(__file__).resolve().parent / "workers" / \
    "flight_worker.py"


@pytest.fixture(autouse=True)
def _fresh():
    monitor.reset()
    monitor.enable(True)
    trace.enable(True)
    trace.reset()
    flight.get_recorder().clear()
    faults.set_plan(None)
    yield
    faults.set_plan(None)
    trace.enable(False)
    trace.reset()
    monitor.reset()
    monitor.refresh()
    trace.refresh()


# ---------------------------------------------------------------------------
# span API
# ---------------------------------------------------------------------------

def test_span_nesting_and_identity():
    with trace.span("t/outer", k=1) as outer:
        assert trace.current_span() is outer
        with trace.span("t/inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            time.sleep(0.005)
    assert trace.current_span() is None
    spans = trace.get_trace(outer.trace_id)
    assert [s["name"] for s in spans] == ["t/outer", "t/inner"]
    by_name = {s["name"]: s for s in spans}
    assert by_name["t/outer"]["parent_id"] is None
    assert by_name["t/outer"]["attrs"] == {"k": 1}
    assert by_name["t/inner"]["dur_us"] >= 4000
    # outer covers inner on the same timebase
    assert by_name["t/outer"]["ts_us"] <= by_name["t/inner"]["ts_us"]
    assert by_name["t/outer"]["dur_us"] >= by_name["t/inner"]["dur_us"]


def test_span_error_annotation():
    with pytest.raises(ValueError):
        with trace.span("t/fails") as s:
            raise ValueError("boom")
    rec = trace.get_trace(s.trace_id)[0]
    assert rec["attrs"]["error"] == "ValueError"


def test_manual_span_and_separate_traces():
    a = trace.start_span("t/a")
    b = trace.start_span("t/b")
    assert a.trace_id != b.trace_id       # no parent → distinct traces
    child = trace.start_span("t/a_child", parent=a)
    child.end()
    a.end()
    b.end(tokens=3)
    assert {s["name"] for s in trace.get_trace(a.trace_id)} == \
        {"t/a", "t/a_child"}
    assert trace.get_trace(b.trace_id)[0]["attrs"] == {"tokens": 3}


def test_end_is_idempotent():
    s = trace.start_span("t/once")
    s.end()
    dur = s.dur_us
    s.end(extra=1)                        # second end: no re-record
    assert s.dur_us == dur
    spans = trace.get_trace(s.trace_id)
    assert len(spans) == 1 and "extra" not in spans[0]["attrs"]


def test_context_propagation_across_threads():
    root = trace.start_span("t/root")
    seen = {}

    def worker():
        # worker thread starts with NO context of its own...
        seen["before"] = trace.current_span()
        with trace.attach(root):
            with trace.span("t/thread_child") as c:
                seen["child"] = c
        seen["after"] = trace.current_span()

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    root.end()
    assert seen["before"] is None and seen["after"] is None
    assert seen["child"].trace_id == root.trace_id
    assert seen["child"].parent_id == root.span_id
    spans = trace.get_trace(root.trace_id)
    assert {s["name"] for s in spans} == {"t/root", "t/thread_child"}


def test_disabled_overhead_guard():
    """A disabled span must stay < 1 µs amortized so PTPU_TRACE=0 can
    never regress a hot path (the PR-1 guard, tracing edition)."""
    trace.enable(False)
    try:
        n, per_call = 50_000, float("inf")
        for _ in range(4):           # min-of-rounds: a loaded shared
            t0 = time.perf_counter()  # host must not flake the bound
            for i in range(n):
                with trace.span("t/overhead", step=i):
                    pass
            per_call = min(per_call, (time.perf_counter() - t0) / n)
    finally:
        trace.enable(True)
    assert per_call < 1e-6, f"disabled span costs {per_call*1e9:.0f} ns"
    assert trace.get_trace("t/overhead") == []   # nothing recorded


def test_disabled_records_nothing():
    trace.enable(False)
    s = trace.start_span("t/phantom")
    with trace.span("t/phantom2"):
        pass
    s.end()
    trace.enable(True)
    assert not s                             # the null singleton is falsy
    assert trace.trace_ids() == []


def test_trace_store_is_bounded():
    for i in range(trace._MAX_TRACES + 20):
        trace.start_span("t/flood").end()
    assert len(trace.trace_ids()) <= trace._MAX_TRACES


def test_chrome_export_merges_profiler_events(tmp_path):
    from paddle_tpu import profiler

    with profiler.Profiler(timer_only=True):
        with profiler.RecordEvent("host/op"):
            pass
        with trace.span("t/framework") as s:
            pass
        path = str(tmp_path / "merged.json")
        prof_export = str(tmp_path / "prof.json")
        trace.export_chrome_trace(path)
    events = json.load(open(path))["traceEvents"]
    names = [e["name"] for e in events]
    assert "host/op" in names and "t/framework" in names
    fw = [e for e in events if e["name"] == "t/framework"][0]
    assert fw["args"]["trace_id"] == s.trace_id
    assert {"ph", "ts", "dur", "pid", "tid"} <= set(fw)
    # and the profiler's own chrome export picks up framework spans too
    prof = profiler.Profiler(timer_only=True)
    prof._export_chrome(prof_export)
    names2 = [e["name"] for e in json.load(open(prof_export))["traceEvents"]]
    assert "t/framework" in names2


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_ring_records_spans_and_notes_bounded():
    rec = flight.get_recorder()
    for i in range(rec.maxlen + 50):
        trace.start_span("t/ring").end()
    flight.note("checkpoint", step=7)
    records = rec.records()
    assert len(records) == rec.maxlen        # bounded
    json.dumps(records)                      # ring is dump-serializable
    assert records[-1]["kind"] == "note"
    assert records[-1]["event"] == "checkpoint"
    assert all(r["kind"] in ("span", "note") for r in records)


def test_dump_is_parseable_and_complete(tmp_path):
    monitor.counter("t/dumped").inc(3)
    with trace.span("t/pre_dump"):
        pass
    path = flight.dump("unit", dir=str(tmp_path))
    doc = json.load(open(path))
    assert doc["reason"] == "unit" and doc["pid"] == os.getpid()
    assert any(r.get("name") == "t/pre_dump" for r in doc["ring"])
    assert doc["metrics"]["t/dumped"] == 3.0
    assert any("test_dump_is_parseable" in "\n".join(frames)
               for frames in doc["stacks"].values())


def test_sigterm_subprocess_leaves_flight_dump(tmp_path):
    """ISSUE 5 acceptance (c): kill -TERM → a parseable dump with the
    last spans is on disk (the resilience workers' subprocess pattern)."""
    env = dict(os.environ)
    env.update(PTPU_FLIGHT_DIR=str(tmp_path), PTPU_TRACE="1",
               PTPU_FORCE_PLATFORM="cpu", JAX_PLATFORMS="cpu")
    env.pop("PTPU_FAULTS", None)
    proc = subprocess.Popen([sys.executable, str(_WORKER)], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline().strip()
        assert line == "READY", line
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == -signal.SIGTERM             # chained default disposition
    dumps = sorted(tmp_path.glob("flight_*_sigterm_*.json"))
    assert dumps, list(tmp_path.iterdir())
    doc = json.load(open(dumps[0]))
    assert doc["reason"] == "sigterm"
    span_names = [r["name"] for r in doc["ring"] if r["kind"] == "span"]
    assert "worker/tick" in span_names
    assert any(r.get("event") == "worker_ready" for r in doc["ring"]
               if r["kind"] == "note")


def test_watchdog_ignores_healthy_process(tmp_path):
    w = monitor.watchdog(stall_s=0.5, dir=str(tmp_path), interval=0.05)
    try:
        for _ in range(6):
            trace.heartbeat()
            time.sleep(0.05)
    finally:
        w.stop()
    assert w.dump_paths == [] and not list(tmp_path.iterdir())


# ---------------------------------------------------------------------------
# serving integration (tiny GPT on CPU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config

    paddle.seed(0)
    m = GPTForCausalLM(gpt_test_config(stacked_blocks=True,
                                       sequence_parallel=False))
    m.eval()
    return m


_PROMPT_LEN = 6      # every test below uses this length, so the module
#                      shares ONE set of jitted step programs


@pytest.fixture(scope="module")
def eng(model):
    """One engine, pre-warmed (compiles are the dominant cost on CPU);
    the tests exercise tracing, which rides the warm step programs."""
    from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

    e = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4))
    rng = np.random.RandomState(9)
    warm = rng.randint(0, model.cfg.vocab_size,
                       (_PROMPT_LEN,)).astype(np.int32)
    prev = trace.enabled()
    trace.enable(False)
    try:
        e.generate([warm], SamplingParams(max_new_tokens=2))
    finally:
        trace.enable(prev)
    return e


def _prompt(model, seed):
    rng = np.random.RandomState(seed)
    return rng.randint(0, model.cfg.vocab_size,
                       (_PROMPT_LEN,)).astype(np.int32)


def test_serving_request_trace_parity(model, eng):
    """A SOLO traced request decomposes into queue_wait → prefill →
    decode steps under one trace_id, parent-linked, and the child span
    durations sum to ≈ the root's wall time (no large unattributed
    gap).  TTFT/TPOT histograms come out nonzero with percentiles."""
    from paddle_tpu.serving import SamplingParams

    prompt = _prompt(model, 0)
    new = 5
    monitor.reset()
    rid = eng.add_request(prompt, SamplingParams(max_new_tokens=new))
    while eng.has_unfinished():
        eng.step()
    out = eng.request_output(rid)
    eng.release_request(rid)
    assert len(out) == _PROMPT_LEN + new

    spans = eng.request_trace(rid)
    roots = [s for s in spans if s["parent_id"] is None]
    assert len(roots) == 1
    root = roots[0]
    assert root["name"] == "serving/request"
    assert root["attrs"]["finish"] == "stop"
    assert root["attrs"]["tokens"] == new
    assert all(s["trace_id"] == root["trace_id"] for s in spans)
    ids = {s["span_id"] for s in spans}
    assert all(s["parent_id"] in ids for s in spans
               if s["parent_id"] is not None)
    names = [s["name"] for s in spans]
    assert names.count("serving/queue_wait") == 1
    assert names.count("serving/prefill") == 1
    # first token samples at prefill end; the rest are decode steps
    assert names.count("serving/decode_step") == new - 1
    # ... and the engine steps it rode cover the same time again
    assert names.count("serving/step") == new

    # a step's span runs from its dispatch to its token's emit, and the
    # next step is dispatched in between: neighbours overlap, so the time
    # they cover is their union
    covered, end = 0.0, 0.0
    for s in sorted((s for s in spans
                     if s["name"] in ("serving/queue_wait",
                                      "serving/prefill",
                                      "serving/decode_step")),
                    key=lambda s: s["ts_us"]):
        lo, hi = max(s["ts_us"], end), s["ts_us"] + s["dur_us"]
        covered += max(0.0, hi - lo)
        end = max(end, hi)
    assert covered <= root["dur_us"] * 1.05
    assert covered >= root["dur_us"] * 0.5, (
        f"unattributed gap: children cover {covered:.0f}us of "
        f"root {root['dur_us']:.0f}us")

    snap = monitor.snapshot()
    assert snap["serving/ttft"]["count"] == 1
    assert snap["serving/ttft"]["sum"] > 0
    assert snap["serving/tpot"]["count"] == new - 1
    assert "p50" in snap["serving/tpot"] and "p95" in snap["serving/tpot"]


def test_request_trace_empty_when_tracing_off(model, eng):
    from paddle_tpu.serving import SamplingParams

    trace.enable(False)
    try:
        rid = eng.add_request(_prompt(model, 1),
                              SamplingParams(max_new_tokens=2))
        while eng.has_unfinished():
            eng.step()
        out = eng.request_output(rid)
        eng.release_request(rid)
    finally:
        trace.enable(True)
    assert len(out) == _PROMPT_LEN + 2 and eng.request_trace(rid) == []


def test_aborted_request_trace_ends_with_abort(model, eng):
    from paddle_tpu.serving import SamplingParams

    rid = eng.add_request(_prompt(model, 2),
                          SamplingParams(max_new_tokens=8))
    eng.step()                       # prefill only
    eng.release_request(rid)         # abort mid-flight
    spans = eng.request_trace(rid)
    root = [s for s in spans if s["name"] == "serving/request"][0]
    assert root["attrs"]["finish"] == "abort"


# ---------------------------------------------------------------------------
# serving phases (ISSUE 26): one boundary, three clocks
# ---------------------------------------------------------------------------

ENGINE_PHASES = ("engine/schedule", "engine/prepare",
                 "engine/sample_dispatch", "engine/readback",
                 "engine/emit", "engine/retire")
API_PHASES = ("api/drain_submits", "api/push_progress")


def _run_two(model, eng, new=(4, 3)):
    """Two requests stepped to the end -> (request ids, steps taken)."""
    from paddle_tpu.serving import SamplingParams

    rids = [eng.add_request(_prompt(model, 20 + i),
                            SamplingParams(max_new_tokens=n))
            for i, n in enumerate(new)]
    steps = 0
    while eng.has_unfinished():
        eng.step()
        steps += 1
    for rid in rids:
        eng.release_request(rid)
    return rids, steps


@pytest.fixture()
def phase_run(model, eng):
    """Tracing OFF (the driver's setting): only the always-on part."""
    trace.enable(False)
    monitor.reset()
    try:
        _, steps = _run_two(model, eng)
    finally:
        trace.enable(True)
    return steps, monitor.snapshot()


@pytest.mark.parametrize("name", ENGINE_PHASES)
def test_engine_phase_counted_once_a_step(phase_run, name):
    calls, snap = phase_run
    # 2 prefill steps + 3 decode steps: each ran a program and sampled;
    # one call more reads the last of them back (a step is in flight)
    steps = 5
    assert calls == steps + 1
    # the first call has no step to read back, the last none to schedule
    h = snap[trace.PHASE_METRIC][f"phase={name}"]
    assert h["count"] == steps and h["sum"] > 0
    by_kind = snap["serving/step_time"]
    assert {k: v["count"] for k, v in by_kind.items()} == {
        "phase=prefill": 2, "phase=decode": 3}


def test_phases_cover_the_step_and_nothing_twice(phase_run):
    """The phases are disjoint pieces of step(): all but the last lie
    inside the interval `serving/step_time` measures (engine/retire runs
    on past it, through the gauges), and they leave little of it unnamed."""
    _, snap = phase_run
    by_phase = {n: snap[trace.PHASE_METRIC][f"phase={n}"]["sum"]
                for n in ENGINE_PHASES}
    stepped = sum(v["sum"] for v in snap["serving/step_time"].values())
    named = sum(by_phase.values())
    assert named - by_phase["engine/retire"] <= stepped
    assert named >= 0.7 * stepped, (by_phase, stepped)


@pytest.mark.parametrize("name", ENGINE_PHASES)
def test_engine_phase_cpu_beside_its_wall_time(phase_run, name):
    """`serving/host_cpu{phase}` is the thread's own CPU time inside the
    phase: there for every phase, and never more than the phase lasted
    (the CPU clock is read inside the wall clock's interval)."""
    _, snap = phase_run
    cpu = snap[trace.PHASE_CPU_METRIC][f"phase={name}"]
    wall = snap[trace.PHASE_METRIC][f"phase={name}"]
    assert 0 <= cpu <= wall["sum"] + 1e-5 * wall["count"]


def test_phase_cpu_counts_work_and_not_waiting():
    """A phase that sleeps has wall time and no CPU time; one that spins
    has both."""
    with trace.phase("t/sleeps"):
        time.sleep(0.05)
    with trace.phase("t/spins"):
        end = time.perf_counter() + 0.05
        while time.perf_counter() < end:
            pass
    snap = monitor.snapshot()
    wall, cpu = snap[trace.PHASE_METRIC], snap[trace.PHASE_CPU_METRIC]
    assert wall["phase=t/sleeps"]["sum"] >= 0.05
    assert cpu["phase=t/sleeps"] < 0.01
    # a spin that other threads or the machine interrupt is still work
    assert 0.01 < cpu["phase=t/spins"] <= wall["phase=t/spins"]["sum"]


def test_step_record_once_a_program_step(phase_run):
    """ISSUE 36: beside `serving/step_time{phase}` the engine observes
    `serving/step_wait{phase}`, the part of the step its thread was
    blocked in the readback - the same count, never more seconds, and no
    stall in a sound run."""
    _, snap = phase_run
    steps, waits = snap["serving/step_time"], snap["serving/step_wait"]
    assert {k: v["count"] for k, v in waits.items()} == {
        k: v["count"] for k, v in steps.items()} == {
        "phase=prefill": 2, "phase=decode": 3}
    for kind, w in waits.items():
        assert 0 < w["sum"] <= steps[kind]["sum"]
    # the wait is the readback phase's own duration (its clock reads
    # lie just outside the phase's)
    readback = snap[trace.PHASE_METRIC]["phase=engine/readback"]
    waited = sum(w["sum"] for w in waits.values())
    assert readback["sum"] <= waited <= readback["sum"] + 1e-3
    # (a counter nobody has touched is not in the snapshot at all)
    assert snap.get("serving/host_stalls", 0) == 0
    assert snap.get("serving/host_stall_seconds", 0) == 0


def test_step_span_carries_its_wait_and_host_side(model, eng):
    """With tracing on, the `serving/step` span of a call that reads a
    step back says how long it waited for it and what the host's side of
    that step was; the call that read nothing back says neither."""
    (rid, _), _ = _run_two(model, eng)
    spans = [s for s in eng.request_trace(rid)
             if s["name"] == "serving/step"]
    first, rest = spans[0], spans[1:]
    assert "wait_ms" not in first["attrs"] and "host_ms" not in first["attrs"]
    assert len(rest) == 3
    for s in rest:
        assert s["attrs"]["wait_ms"] >= 0 and s["attrs"]["host_ms"] >= 0
        readback, = [k for k in eng.request_trace(rid)
                     if k["parent_id"] == s["span_id"]
                     and k["name"] == "engine/readback"]
        assert s["attrs"]["wait_ms"] >= readback["dur_us"] / 1e3 - 1e-3


def test_injected_stall_is_the_hosts_and_no_phase_sees_it(model, eng):
    """A stall between two phases of ONE call of step() (the fault plan's
    `stall@site=engine.step` sleeps before the first phase opens): the
    step record counts one host stall of its length, `step_time -
    step_wait` holds it, no phase's `host_time` does, and the flight ring
    names the step."""
    from paddle_tpu.serving import SamplingParams

    trace.enable(False)
    rids = [eng.add_request(_prompt(model, 40 + i),
                            SamplingParams(max_new_tokens=6))
            for i in range(2)]
    for _ in range(3):
        eng.step()                     # both prefilled, a decode in flight
    flight.get_recorder().clear()
    before = monitor.snapshot()
    faults.set_plan(faults.FaultPlan("stall@site=engine.step,secs=0.4"))
    try:
        eng.step()                     # the plan fires once
        while eng.has_unfinished():
            eng.step()
    finally:
        faults.set_plan(None)
        for rid in rids:
            eng.release_request(rid)
    after = monitor.snapshot()

    def gained(name, label=None, part=None):
        def value(snap):
            v = snap.get(name, 0)
            v = v.get(label, 0) if label else v
            return v[part] if part and v else v
        return value(after) - value(before)

    assert gained("serving/host_stalls") == 1
    assert gained("serving/host_stall_seconds") >= 0.4
    host_side = sum(
        gained("serving/step_time", f"phase={k}", "sum")
        - gained("serving/step_wait", f"phase={k}", "sum")
        for k in ("prefill", "decode"))
    assert host_side >= 0.4
    for name in ENGINE_PHASES:
        assert gained(trace.PHASE_METRIC, f"phase={name}", "sum") < 0.2, name
    note, = [r for r in flight.get_recorder().records()
             if r.get("event") == "host_stall"]
    assert note["phase"] == "decode" and note["rows"] == 2
    assert note["host_s"] >= 0.4 and 0 <= note["wait_s"] < 0.2


def test_request_trace_shows_the_steps_it_rode(model, eng):
    """With tracing on, every engine step is ONE `serving/step` span filed
    under each rider's trace as a child of its root; the step's phases
    are its children, inside it, in order, not overlapping."""
    (rid_a, rid_b), steps = _run_two(model, eng)
    a, b = eng.request_trace(rid_a), eng.request_trace(rid_b)
    root = [s for s in a if s["name"] == "serving/request"][0]
    step_spans = [s for s in a if s["name"] == "serving/step"]
    # a rode its prefill step and 3 decode steps, not b's prefill
    assert len(step_spans) == 4 < steps
    assert [s["attrs"]["phase"] for s in step_spans] == \
        ["prefill", "decode", "decode", "decode"]
    assert all(s["parent_id"] == root["span_id"]
               and s["trace_id"] == root["trace_id"] for s in step_spans)
    own = {s["attrs"]["step"] for s in a
           if s["name"] in ("serving/prefill", "serving/decode_step")}
    assert own == {s["span_id"] for s in step_spans}
    for n, st in enumerate(step_spans):
        kids = [s for s in a if s["parent_id"] == st["span_id"]]
        # the call that dispatched a's prefill had no step to read back;
        # every later one read back the step before the one it dispatched
        assert [k["name"] for k in kids] == list(
            ENGINE_PHASES if n else ENGINE_PHASES[:3])
        assert st["attrs"]["rows"] == len(st["attrs"]["trace_ids"])
        assert root["trace_id"] in st["attrs"]["trace_ids"]
        end = st["ts_us"]
        for k in kids:                   # nested, ordered, disjoint
            assert k["ts_us"] >= end - 1e-3
            end = k["ts_us"] + k["dur_us"]
        assert end <= st["ts_us"] + st["dur_us"] + 1e-3
    # the two decode steps both rode are the SAME spans in both traces
    shared = [s for s in step_spans if s["attrs"]["rows"] == 2]
    assert len(shared) == 2
    assert {s["span_id"] for s in shared} <= {s["span_id"] for s in b}
    # and the all-traces export holds each of them once
    ids = [e["args"]["span_id"] for e in trace.chrome_events()]
    assert len(ids) == len(set(ids))


def test_phase_is_an_event_of_an_open_profiler_session(model, eng, tmp_path):
    """Inside a jax.profiler session the phases are host events of the
    session's xplane (on the device trace's clock), named `ptpu:<phase>`;
    `profiler.RecordEvent` goes through the same annotation."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from paddle_tpu import profiler

    assert trace.annotation("outside") is None      # no session open
    jax.profiler.start_trace(str(tmp_path))
    try:
        _run_two(model, eng, new=(2,))
        with profiler.RecordEvent("host/record_event"):
            pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    for name in ENGINE_PHASES:
        assert trace.PHASE_PREFIX + name in names, sorted(
            n for n in names if n.startswith(trace.PHASE_PREFIX))
    assert "host/record_event" in names


def test_api_pump_phases_and_submit_wait(model, eng):
    """One streamed completion through the HTTP front door: the pump's
    two phases are counted, and `serving/submit_wait` holds the one wait
    between the handler's put and the pump's add_request."""
    from paddle_tpu.serving.api import ApiServer

    monitor.reset()
    srv = ApiServer(engine=eng, api_keys={}, poll_s=0.005)
    try:
        req = urllib.request.Request(
            srv.url + "/v1/completions",
            data=json.dumps({"prompt": [int(t) for t in _prompt(model, 30)],
                             "max_tokens": 3, "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        body = urllib.request.urlopen(req, timeout=60).read().decode()
    finally:
        srv.stop()
    assert body.rstrip().endswith("data: [DONE]")
    snap = monitor.snapshot()
    for name in API_PHASES:
        assert snap[trace.PHASE_METRIC][f"phase={name}"]["count"] >= 3
    wait = snap["serving/submit_wait"]
    assert wait["count"] == 1 and 0 <= wait["sum"] < 5.0
    assert snap["serving/queue_wait"]["count"] == 1


def test_phase_disabled_overhead_guard():
    """Monitor off, tracing off, no profiler session: a phase is the
    no-op singleton, under the bound of the module's other guards."""
    trace.enable(False)
    monitor.enable(False)
    try:
        n, per_call = 50_000, float("inf")
        for _ in range(4):
            t0 = time.perf_counter()
            for _ in range(n):
                with trace.phase("t/overhead"):
                    pass
            per_call = min(per_call, (time.perf_counter() - t0) / n)
    finally:
        monitor.enable(True)
        trace.enable(True)
    assert per_call < 1e-6, f"disabled phase costs {per_call*1e9:.0f} ns"
    assert "phase=t/overhead" not in monitor.snapshot().get(
        trace.PHASE_METRIC, {})


def test_phase_outside_a_span_opens_no_trace():
    """Tracing on but no span open (the API pump between steps): the
    phase is counted, and floods no trace into the bounded store."""
    with trace.phase("t/loose"):
        pass
    assert trace.trace_ids() == []
    assert monitor.snapshot()[trace.PHASE_METRIC]["phase=t/loose"][
        "count"] == 1


def test_watchdog_dumps_on_injected_stall(model, eng, tmp_path, monkeypatch):
    """ISSUE 5 acceptance: a PTPU_FAULTS stall inside engine.step —
    no span/step completes — trips the watchdog, which dumps ring +
    all-thread py-stacks showing exactly where the process hangs."""
    from paddle_tpu.serving import SamplingParams

    monkeypatch.setenv("PTPU_FLIGHT_DIR", str(tmp_path))
    prompt = _prompt(model, 3)
    faults.set_plan(faults.FaultPlan("stall@site=engine.step,secs=1.0"))
    w = monitor.watchdog(stall_s=0.25, interval=0.05)
    try:
        eng.generate([prompt], SamplingParams(max_new_tokens=2))
    finally:
        w.stop()
        faults.set_plan(None)
    assert w.dump_paths, "watchdog never fired during the injected stall"
    doc = json.load(open(w.dump_paths[0]))
    assert doc["reason"] == "stall"
    assert doc["extra"]["stalled_for_s"] >= 0.25
    all_frames = "\n".join(ln for frames in doc["stacks"].values()
                           for ln in frames)
    assert "maybe_stall" in all_frames, "stacks must show the hang site"
    assert monitor.snapshot()["monitor/watchdog_dumps"] >= 1


# ---------------------------------------------------------------------------
# live endpoint
# ---------------------------------------------------------------------------

def test_endpoint_metrics_healthz_traces():
    from paddle_tpu.monitor import serve

    monitor.counter("t/served").inc(2)
    with trace.span("t/served_span") as s:
        pass
    srv = serve.MonitorServer(port=0)   # private instance: no global state
    try:
        txt = urllib.request.urlopen(srv.url + "/metrics",
                                     timeout=10).read().decode()
        assert "t_served 2" in txt
        hz = json.loads(urllib.request.urlopen(srv.url + "/healthz",
                                               timeout=10).read())
        assert hz["status"] == "ok" and hz["pid"] == os.getpid()
        assert hz["last_activity_age_s"] >= 0
        spans = json.loads(urllib.request.urlopen(
            srv.url + "/traces/" + s.trace_id, timeout=10).read())
        assert spans[0]["name"] == "t/served_span"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/traces/nope", timeout=10)
        assert ei.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(srv.url + "/whatever", timeout=10)
        assert ei.value.code == 404
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# CI surface: lint + smoke script
# ---------------------------------------------------------------------------

def test_lint_metrics_repo_clean_and_catches_violations(tmp_path):
    tools = pathlib.Path(__file__).resolve().parent.parent / "tools"
    proc = subprocess.run([sys.executable, str(tools / "lint_metrics.py")],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    bad = tmp_path / "bad.py"
    bad.write_text(
        'import monitor\n'
        'monitor.counter("NoSlash").inc()\n'
        'monitor.gauge(f"dyn/{x}").set(1)\n'
        'monitor.counter("a/b").labels(**kw).inc()\n')
    proc = subprocess.run(
        [sys.executable, str(tools / "lint_metrics.py"), str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "convention" in proc.stdout
    assert "dynamic metric name" in proc.stdout
    assert "labels(**dict)" in proc.stdout


# serve_smoke --trace (ISSUE 5 acceptance (a)+(b) end-to-end, asserted
# in-script) is exercised by tests/test_serving.py::test_serve_smoke_script,
# which runs the ONE fast-tier smoke subprocess in trace mode — trace mode
# is a strict superset of the plain smoke assertions, and a second
# engine-compiling subprocess here would double the suite's dominant cost.
