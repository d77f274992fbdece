"""The five per-layer metrics that read the engine's step record
(`serving/step_wait{phase}`, `serving/host_cpu{phase}`, `serving/host_stalls`,
`serving/host_stall_seconds`, `serving/steps_dispatched{in_flight}`): each
reader on hand-made counters, on none, on a program of before the record,
and on what a tiny engine served on the CPU really leaves in
`monitor.snapshot()`.  The manifest's entries are found by NAME, so the next
appended entry turns nothing here red.  Nothing here is a measurement."""
import importlib.util
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import host_phases, step_record  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

SERVE_CELLS = {"gpt3-1.3b.chat-c16", "gpt3-6.7b-l16.docqa-c8",
               "trinity-large-ep8-l5.longmix-c32",
               "lfm2-24b-a2b-l9.agents-c64",
               "mistral-small-4-ep8-l8.longctx-c64"}
ALL_PHASES = step_record.BUSY_PHASES + ("engine/readback",
                                        "api/drain_submits")


def _load(directory, name):
    path = os.path.join(BENCH, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"_bench_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _phase(name, seconds, cpu, count=10):
    return {f"serving/host_time{{phase={name}}}:sum": seconds,
            f"serving/host_time{{phase={name}}}:count": count,
            f"serving/host_cpu{{phase={name}}}": cpu}


# A window of 8 decode steps of 100 ms, 25 of them waited for the device,
# and 2 prefill steps; 199 of 200 program steps went out behind another;
# the six busy phases lasted 0.2 s and the thread ran for 0.15 of them.
COUNTERS = {
    "serving/step_time{phase=decode}:count": 8,
    "serving/step_time{phase=decode}:sum": 0.8,
    "serving/step_wait{phase=decode}:count": 8,
    "serving/step_wait{phase=decode}:sum": 0.2,
    "serving/step_time{phase=prefill}:count": 2,
    "serving/step_time{phase=prefill}:sum": 0.3,
    "serving/step_wait{phase=prefill}:count": 2,
    "serving/step_wait{phase=prefill}:sum": 0.25,
    "serving/step_time{phase=idle}:count": 1,
    "serving/step_time{phase=idle}:sum": 0.001,
    "serving/steps_dispatched{in_flight=1}": 199,
    "serving/steps_dispatched{in_flight=0}": 1,
    "serving/host_stalls": 1,
    "serving/host_stall_seconds": 0.75,
    **_phase("engine/schedule", 0.02, 0.02),
    **_phase("engine/prepare", 0.10, 0.06),
    **_phase("engine/sample_dispatch", 0.03, 0.02),
    **_phase("engine/emit", 0.02, 0.02),
    **_phase("engine/retire", 0.01, 0.01),
    **_phase("api/push_progress", 0.02, 0.02),
    **_phase("engine/readback", 0.45, 0.001),      # waits by design
    **_phase("api/drain_submits", 0.30, 0.002),    # blocks by design
}
CTX = {"counters": COUNTERS, "timings": {"window_s": 2.0}}
EXPECTED = {
    "step_host_ms": 1e3 * (0.8 - 0.2) / 8,
    "step_wait_share": 100.0 * 0.2 / 0.8,
    "host_cpu_share": 100.0 * 0.15 / 0.20,
    "pipeline_full_share": 99.5,
    "host_stall_s": 0.75,
}
ENTRIES = {      # name -> (unit, better, source, layer)
    "step_host_ms": ("ms", "lower", "program_span", "engine programs"),
    "step_wait_share": ("%", "higher", "program_span", "device"),
    "host_cpu_share": ("%", "higher", "program_counter", "engine programs"),
    "pipeline_full_share": ("%", "higher", "program_counter",
                            "engine programs"),
    "host_stall_s": ("s", "lower", "program_counter", "engine programs"),
}


@pytest.mark.parametrize("stem", sorted(EXPECTED))
def test_reader_on_hand_made_counters(stem):
    assert _load("layer_metrics", stem).compute(CTX) == pytest.approx(
        EXPECTED[stem])


@pytest.mark.parametrize("stem", sorted(EXPECTED))
def test_reader_finds_nothing_without_its_series(stem):
    """No counters at all, and a program of before the step in flight
    (`serving/step_time` and the phases' wall time, nothing else): None,
    and nothing raised."""
    older = {k: v for k, v in COUNTERS.items()
             if k.startswith(("serving/step_time", "serving/host_time"))}
    reader = _load("layer_metrics", stem)
    assert reader.compute({"counters": {}, "timings": {}}) is None
    assert reader.compute({"counters": older, "timings": {}}) is None


def test_parent_of_this_record_reports_the_pipeline_alone():
    """PR 35's program counts `serving/steps_dispatched` and keeps no
    record: that one reader reads it, the other four leave their metric
    out."""
    parent = {k: v for k, v in COUNTERS.items()
              if not k.startswith(("serving/step_wait", "serving/host_cpu",
                                   "serving/host_stall"))}
    got = {stem: _load("layer_metrics", stem).compute(
        {"counters": parent, "timings": {}}) for stem in EXPECTED}
    assert got.pop("pipeline_full_share") == pytest.approx(99.5)
    assert set(got.values()) == {None}


def test_a_sound_run_reads_zero_stall_seconds_not_none():
    """A counter nobody touched is not in the snapshot: with the record
    there and the stall counter not, the run stalled for 0 s."""
    sound = {k: v for k, v in COUNTERS.items()
             if not k.startswith("serving/host_stall")}
    assert _load("layer_metrics", "host_stall_s").compute(
        {"counters": sound, "timings": {}}) == 0.0


def test_a_window_without_a_decode_step_reads_no_host_side():
    prefill_only = {k: v for k, v in COUNTERS.items()
                    if "phase=decode" not in k}
    assert step_record.host_ms(prefill_only) is None
    assert step_record.wait_share(prefill_only) is None
    assert step_record.host_ms(prefill_only, "prefill") == pytest.approx(25.0)


def test_cpu_share_leaves_out_the_phases_that_wait_by_design():
    assert set(step_record.BUSY_PHASES).isdisjoint(
        {"engine/readback", "api/drain_submits"})
    assert step_record.cpu_share(COUNTERS, ALL_PHASES) == pytest.approx(
        100.0 * 0.153 / 0.95)
    assert set(step_record.BUSY_PHASES) < set(
        host_phases.GAP_PHASES + ("engine/sample_dispatch",))


@pytest.mark.parametrize("stem", sorted(ENTRIES))
def test_manifest_entry_of(stem):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == stem + ".serve"]
    unit, better, source, layer = ENTRIES[stem]
    assert (entry["unit"], entry["better"], entry["source"],
            entry["layer"]) == (unit, better, source, layer)
    assert entry["moves"] == "serve_tokens_per_s"
    assert set(entry["workloads"]) >= SERVE_CELLS
    assert os.path.isfile(os.path.join(BENCH, "layer_metrics", stem + ".py"))


def test_every_listed_cell_reports_the_metric_they_move():
    rate, = [m for m in MANIFEST["end_to_end"]
             if m["name"] == "serve_tokens_per_s"]
    for stem in ENTRIES:
        entry, = [m for m in MANIFEST["per_layer"]
                  if m["name"] == stem + ".serve"]
        assert set(entry["workloads"]) <= set(rate["workloads"])


# `kinds/serve._monitor_delta` over a tiny engine behind the HTTP front door
# on the CPU: the counters as a reader really gets them (the fixture of the
# host-phase readers' tests, taken by path: tests/benchmark is no package)
served = _load(os.path.join(os.pardir, "tests", "benchmark"),
               "test_host_phase_metrics").served


@pytest.mark.parametrize("stem", sorted(EXPECTED))
def test_reader_on_a_served_window(served, stem):
    value = _load("layer_metrics", stem).compute(
        {"counters": served, "timings": {"window_s": 60.0}})
    assert value is not None and value >= 0
    if stem.endswith("_share"):
        assert value <= 100.0


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_served_window_records_every_program_step_once(served, kind):
    steps = served[f"serving/step_time{{phase={kind}}}:count"]
    assert steps == served[f"serving/step_wait{{phase={kind}}}:count"] > 0
    assert 0 < served[f"serving/step_wait{{phase={kind}}}:sum"] <= served[
        f"serving/step_time{{phase={kind}}}:sum"]
    assert "serving/step_wait{phase=idle}:count" not in served


@pytest.mark.parametrize("name", ALL_PHASES)
def test_served_window_has_the_cpu_of_every_phase(served, name):
    cpu = served[f"serving/host_cpu{{phase={name}}}"]
    wall = served[f"serving/host_time{{phase={name}}}:sum"]
    count = served[f"serving/host_time{{phase={name}}}:count"]
    assert 0 <= cpu <= wall + 1e-5 * count


def test_served_windows_host_side_holds_its_phases(served):
    """The identity the builder reports from the chip: between two
    readbacks lie the six busy phases and the pump's blocking get, and
    whatever no phase names - so the host's side is at least their sum.
    The first compiles of this tiny engine are host stalls, and the
    record says so."""
    total = sum(served[f"serving/step_time{{phase={k}}}:sum"]
                - served[f"serving/step_wait{{phase={k}}}:sum"]
                for k in ("prefill", "decode"))
    named = host_phases.phase_seconds(
        served, step_record.BUSY_PHASES + ("api/drain_submits",))
    # a step dispatched onto an empty pipeline is timed from its call's
    # start: the pump's phases before that call lie outside every step
    between = host_phases.phase_seconds(
        served, ("api/drain_submits", "api/push_progress"))
    assert total >= named - between
    assert step_record.stall_seconds(served) <= total
