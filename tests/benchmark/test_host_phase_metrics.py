"""The seven per-layer metrics that read the program's serving phases
(`serving/host_time{phase}`, `serving/submit_wait`, `serving/queue_wait`):
each reader on hand-made counters, on none, and on what a tiny engine
stepped on the CPU really leaves in `monitor.snapshot()`.  Nothing here is
a measurement."""
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

from benchmark.lib import host_phases  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

SERVE_CELLS = ["gpt3-1.3b.chat-c16", "gpt3-6.7b-l16.docqa-c8"]


def _load(directory, name):
    path = os.path.join(BENCH, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"_bench_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _phase(name, seconds, count=10):
    return {f"serving/host_time{{phase={name}}}:sum": seconds,
            f"serving/host_time{{phase={name}}}:count": count}


# A window of 2 s: 8 decode steps and 2 prefill steps ran a program, one
# step idled; seconds per phase chosen so every metric reads a round number.
COUNTERS = {
    "serving/step_time{phase=decode}:count": 8,
    "serving/step_time{phase=decode}:sum": 0.8,
    "serving/step_time{phase=prefill}:count": 2,
    "serving/step_time{phase=prefill}:sum": 0.1,
    "serving/step_time{phase=idle}:count": 1,
    "serving/step_time{phase=idle}:sum": 0.001,
    **_phase("api/drain_submits", 0.010),
    **_phase("api/push_progress", 0.020),
    **_phase("engine/schedule", 0.030),
    **_phase("engine/prepare", 0.050),
    **_phase("engine/sample_dispatch", 0.040),     # under the model program
    **_phase("engine/readback", 0.700),            # waiting for the device
    **_phase("engine/emit", 0.060),
    **_phase("engine/retire", 0.010),
    "serving/submit_wait:count": 4, "serving/submit_wait:sum": 0.2,
    "serving/queue_wait:count": 4, "serving/queue_wait:sum": 0.3,
}
CTX = {"counters": COUNTERS, "timings": {"window_s": 2.0}}
EXPECTED = {
    "host_gap_share": 100.0 * 0.18 / 2.0,      # the six gap phases
    "schedule_ms": 1e3 * 0.04 / 10,            # schedule + retire, 10 steps
    "prepare_ms": 1e3 * 0.05 / 10,
    "emit_ms": 1e3 * 0.06 / 10,
    "pump_ms": 1e3 * 0.03 / 10,
    "submit_wait_ms": 50.0,
    "queue_wait_ms": 75.0,
}


@pytest.mark.parametrize("stem", sorted(EXPECTED))
def test_reader_on_hand_made_counters(stem):
    assert _load("layer_metrics", stem).compute(CTX) == pytest.approx(
        EXPECTED[stem])


@pytest.mark.parametrize("stem", sorted(EXPECTED))
def test_reader_finds_nothing_on_a_program_without_the_phases(stem):
    """The parent commit has `serving/step_time` and no phase histogram:
    the reader returns None and does not raise."""
    older = {k: v for k, v in COUNTERS.items()
             if k.startswith("serving/step_time")}
    reader = _load("layer_metrics", stem)
    assert reader.compute({"counters": older,
                           "timings": {"window_s": 2.0}}) is None
    assert reader.compute({"counters": {},
                           "timings": {"window_s": 2.0}}) is None


@pytest.mark.parametrize("stem", sorted(EXPECTED))
def test_manifest_entry_of(stem):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == stem + ".serve"]
    assert entry["workloads"] == SERVE_CELLS
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["better"] == "lower"
    assert entry["source"] == ("program_counter" if "wait" in stem
                               else "program_span")


def test_new_entries_are_appended_in_order():
    names = [m["name"] for m in MANIFEST["per_layer"]]
    assert names[-7:] == ["host_gap_share.serve", "schedule_ms.serve",
                          "prepare_ms.serve", "emit_ms.serve",
                          "pump_ms.serve", "submit_wait_ms.serve",
                          "queue_wait_ms.serve"]


def test_steps_without_a_program_are_not_steps():
    assert host_phases.program_steps(COUNTERS) == 10
    assert host_phases.per_step_ms({**COUNTERS,
                                    "serving/step_time{phase=decode}:count": 0,
                                    "serving/step_time{phase=prefill}:count": 0},
                                   ("engine/emit",)) is None


@pytest.fixture(scope="module")
def served():
    """`kinds/serve._monitor_delta` over a tiny engine behind the HTTP
    front door on the CPU: the counters as a reader really gets them."""
    import urllib.request

    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import monitor
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config
    from paddle_tpu.serving import EngineConfig, LLMEngine
    from paddle_tpu.serving.api import ApiServer

    paddle.seed(0)
    model = GPTForCausalLM(gpt_test_config(stacked_blocks=True,
                                           sequence_parallel=False))
    model.eval()
    engine = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4))
    was = monitor.enabled()
    monitor.enable(True)
    before = monitor.snapshot()
    srv = ApiServer(engine=engine, api_keys={}, poll_s=0.005)
    try:
        for seed in (1, 2):
            ids = np.random.RandomState(seed).randint(
                0, model.cfg.vocab_size, (6,))
            req = urllib.request.Request(
                srv.url + "/v1/completions",
                data=json.dumps({"prompt": [int(t) for t in ids],
                                 "max_tokens": 3}).encode(),
                headers={"Content-Type": "application/json"})
            assert urllib.request.urlopen(req, timeout=120).status == 200
    finally:
        srv.stop()
        after = monitor.snapshot()
        monitor.enable(was)
    return _load("kinds", "serve")._monitor_delta(before, after)


@pytest.mark.parametrize("stem", sorted(EXPECTED))
def test_reader_on_a_served_window(served, stem):
    value = _load("layer_metrics", stem).compute(
        {"counters": served, "timings": {"window_s": 60.0}})
    assert value is not None and value >= 0


def test_served_window_names_every_phase(served):
    steps = host_phases.program_steps(served)
    assert steps == 6                  # two requests: 1 prefill + 2 decodes
    for name in host_phases.GAP_PHASES + ("engine/sample_dispatch",
                                          "engine/readback"):
        count = served[f"serving/host_time{{phase={name}}}:count"]
        # the pump turns (and the engine may idle) between requests too
        assert count >= steps if name.startswith("api/") else count == steps
    assert served["serving/submit_wait:count"] == 2
    assert served["serving/queue_wait:count"] == 2
