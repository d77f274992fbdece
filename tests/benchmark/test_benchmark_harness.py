"""The benchmark's own tests: the manifest is coherent, the yardstick's
arithmetic is right, and each kind of runner runs for a second at toy
widths on the CPU.  No test here needs a chip, and none of their numbers is
a measurement."""
import importlib.util
import json
import os
import re
import subprocess
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
END_TO_END = {m["name"]: m for m in MANIFEST["end_to_end"]}


def _load(directory, name):
    path = os.path.join(BENCH, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"_bench_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _reports(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_manifest_shape():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert MANIFEST["command"][1].startswith(MANIFEST["paths"][0] + "/")
    cells = MANIFEST["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    assert used == {c["name"] for c in MANIFEST["configs"]}
    names = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in END_TO_END and "workloads" not in END_TO_END["setup_s"]


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_cell_resolves_to_files(cell):
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell["config"])
    config = _json(ROOT, entry["file"])
    assert entry["file"].startswith("benchmark/configs/")
    assert config["reduced"] == entry["reduced"]
    traffic = _json(BENCH, "traffic", cell["traffic"] + ".json")
    assert hasattr(_load("kinds", traffic["kind"]), "run")
    e2e = [m["name"] for m in MANIFEST["end_to_end"]
           if _reports(m, cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(_reports(m, cell["name"]) for m in MANIFEST["per_layer"])


@pytest.mark.parametrize("metric", MANIFEST["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1


@pytest.mark.parametrize("metric", MANIFEST["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "source",
                                           "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    reader = _load("layer_metrics", metric["name"].split(".")[0])
    assert callable(reader.compute) and reader.__doc__
    moved = END_TO_END[metric["moves"]]
    cells = metric.get("workloads") or [w["name"]
                                        for w in MANIFEST["workloads"]]
    known = {w["name"] for w in MANIFEST["workloads"]}
    for cell in cells:
        assert cell in known and _reports(moved, cell)


def test_stats_percentiles_and_gaps():
    from benchmark.lib import stats

    assert stats.percentile([4, 1, 3, 2], 50) == (2.5, 4)
    assert stats.percentile(range(1, 102), 95) == (96.0, 101)
    assert stats.percentile([7.0], 95) == (7.0, 1)
    assert stats.median([5, 1, 9]) == 5
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    # two requests; the second got two tokens in one chunk (gap 0)
    gaps = stats.pooled_gaps([[0.0, 0.25, 0.75], [1.0, 1.0, 1.5], [2.0]])
    assert gaps == [0.25, 0.5, 0.0, 0.5]


def test_traffic_same_work_for_every_seed():
    from benchmark.lib.traffic import Requests

    traffic = _json(BENCH, "traffic", "chat-c16.json")
    a, b = (Requests(traffic, 50304, seed) for seed in (1, 2))
    assert a.deck_size == 20
    deck_a = [a.shape(i) for i in range(20)]
    deck_b = [b.shape(i) for i in range(20)]
    # the same 20 pairs for every seed and every deck, in another order
    assert deck_a != deck_b and deck_a != [a.shape(i) for i in range(20, 40)]
    for deck in (deck_a, deck_b, [a.shape(i) for i in range(20, 40)]):
        assert sorted(deck) == a.pairs
    assert sorted(p for p, _ in a.pairs) == sorted(
        [128] * 6 + [256] * 6 + [512] * 5 + [1024] * 3)
    assert sorted(m for _, m in a.pairs) == sorted(
        ([32] * 3 + [64] * 3 + [128] * 3 + [256]) * 2)
    assert [m for p, m in a.pairs if p == 1024] == [32, 64, 128]
    ids, max_tokens = a.request(7)
    assert (ids, max_tokens) == Requests(traffic, 50304, 1).request(7)
    assert len(ids) == deck_a[7][0] and max(ids) < 50304
    assert a.prompt_lengths() == [128, 256, 512, 1024]


def test_trace_reduction_on_recorded_fixture():
    """A slice cut from this PR's first chip trace of the train cell
    (tests/benchmark/fixtures/trace_slice.json; times in ns)."""
    from benchmark.lib import trace

    events = _json(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                   "trace_slice.json")
    expect = events.pop("expect")
    s = trace.summarize(events)
    assert s["window_s"] == pytest.approx(expect["window_s"])
    assert s["busy_s"] == pytest.approx(expect["busy_s"])
    assert s["idle_share"] == pytest.approx(expect["idle_share"])
    assert s["device_ops"][0][0] == expect["top_op"]
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    assert s["idle_gaps"][0][0] == expect["top_gap"]
    assert sum(v for _, v in s["idle_gaps"]) <= (
        s["window_s"] - s["busy_s"]) * (1 + 1e-9)
    for pred, key in ((trace.is_pallas, "pallas_share"),
                      (trace.is_optimizer_update, "optimizer_share")):
        assert trace.share_of(events, pred) == pytest.approx(expect[key])
    assert trace.summarize({"devices": {}, "host": []}) is None


def test_trace_names_and_rules():
    from benchmark.lib import trace

    adam = ("%subtract_convert_fusion.1 = (bf16[24,2048,8192]{2,1,0:T(8,128)"
            "(2,1)}, bf16[24,2048,8192]{2,1,0:T(8,128)(2,1)}, bf16[24,2048,"
            "8192]{2,1,0:T(8,128)(2,1)}) fusion(bf16[24,2048,8192]{2,1,0} "
            "%state_vals_10_.1, f32[]{:T(128)S(6)} %sub.7), kind=kLoop")
    assert trace.short_name(adam) == ("subtract_convert_fusion (bf16[24,"
                                      "2048,8192], bf16[24,2048,8192], "
                                      "bf16[24,2048,8192])")
    assert trace.is_optimizer_update(adam) and not trace.is_pallas(adam)
    matmul = ("%fusion.13 = bf16[50304,2048]{1,0:T(8,128)(2,1)} fusion("
              "bf16[2,2048,50304]{2,1,0} %get-tuple-element.851, bf16[2048]"
              " %state_vals_3_.1), kind=kOutput")
    assert trace.short_name(matmul) == "fusion bf16[50304,2048]"
    assert not trace.is_optimizer_update(matmul)
    kernel = ("%custom-call.7 = bf16[2,16,2048,128]{3,2,1,0} custom-call("
              "bf16[2,16,2048,128] %a), custom_call_target=\"tpu_custom_call\"")
    assert trace.is_pallas(kernel)
    assert not trace.is_pallas(kernel.replace("tpu_custom_call",
                                              "ConcatBitcast"))
    # overlapping events are counted once; the gap is labelled by the
    # bench: span over its middle and by the operations around it
    events = {"devices": {"/device:TPU:0": [["%a = f32[1] x()", 0, 100],
                                            ["%b = f32[1] y()", 50, 100],
                                            ["%c = f32[1] z()", 250, 50]]},
              "host": [["bench:wait", 140, 100], ["other", 0, 1000]]}
    s = trace.summarize(events)
    assert s["window_s"] == pytest.approx(300e-9)
    assert s["busy_s"] == pytest.approx(200e-9)
    assert s["idle_gaps"] == [["wait | b f32[1] -> c f32[1]",
                               pytest.approx(100e-9)]]


def test_flops_and_peaks_from_shapes():
    from benchmark.lib import flops
    from benchmark.lib.common import model_config
    from benchmark.lib.peaks import peaks_for
    from paddle_tpu.models import gpt3_1p3b_config, gpt3_6p7b_config

    small = _json(BENCH, "configs", "gpt3-1.3b.json")
    cfg = model_config(small)
    assert cfg == gpt3_1p3b_config(stacked_blocks=True)
    wide = model_config(_json(BENCH, "configs", "gpt3-6.7b-l16.json"))
    assert wide == gpt3_6p7b_config(stacked_blocks=True,
                                    num_hidden_layers=16)
    # 24 * 12 * 2048^2 in the blocks' matrices, 50304 * 2048 in the head
    assert flops.matmul_params(small) == 24 * 12 * 2048 ** 2 + 50304 * 2048
    assert 1.31e9 < flops.total_params(small) < 1.32e9
    assert flops.train_flops_per_token(small, 2048) == (
        6 * flops.matmul_params(small) + 6 * 24 * 2048 * 2048)
    import numpy as np
    from benchmark.lib.common import build_model

    model, _ = build_model(TINY, seed=3)       # every parameter, counted
    assert flops.total_params(TINY) == sum(
        int(np.prod(p.shape)) for p in model.parameters())
    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


TINY = {"vocab_size": 128, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 128,
        "max_position_embeddings": 256, "initializer_range": 0.02,
        "layer_norm_epsilon": 1e-5,
        "harness": {"constructor": "paddle_tpu.models:GPTConfig",
                    "kwargs": {"stacked_blocks": True,
                               "sequence_parallel": False},
                    "dtype": "float32"}}
TINY_TRAFFIC = {
    "train": {"kind": "train", "seq_len": 64, "micro_batch": 2,
              "learning_rate": 1e-4, "multi_precision": False,
              "distinct_batches": 4, "trace_steps": 2},
    "serve": {"kind": "serve", "clients": 3,
              "prompt_len": [[16, 2], [32, 1]],
              "max_tokens": [[4, 1], [8, 1]],
              "engine": {"block_size": 16, "max_num_seqs": 4},
              "warmup_s": 0.5, "trace_s": 0.5, "check_requests": 4},
}


@pytest.mark.parametrize("kind", ["train", "serve"])
def test_runner_one_second_on_cpu(kind, monkeypatch):
    """Each kind's runner, in-process, at toy widths: control flow, the
    reference comparison and the bookkeeping.  Its rates are CPU numbers and
    mean nothing; the kernel-path check cannot pass off the chip."""
    from benchmark.lib.common import CompileCounter

    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    out = _load("kinds", kind).run({
        "cell": {"name": "tiny"}, "config": TINY,
        "traffic": TINY_TRAFFIC[kind], "seed": 2 ** 31 + 11, "seconds": 1.0,
        "trace": False, "t0": time.perf_counter(),
        "compiles": CompileCounter()})
    checks = dict(out["checks"])
    assert checks.pop("kernel_paths") is False          # no Pallas on a CPU
    assert all(checks.values()), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["timings"]["window_s"] >= 1.0
    assert out["end_to_end"]["setup_s"] > 0
    ctx = {"counters": out["counters"], "timings": out["timings"],
           "end_to_end": out["end_to_end"], "config": TINY, "chips": 1,
           "peaks": {"bf16_flops_per_s": 1e12}}
    if kind == "train":
        assert out["end_to_end"]["train_tokens_per_s"] == pytest.approx(
            out["timings"]["steps"] * 128 / out["timings"]["window_s"])
        assert _load("layer_metrics", "mfu").compute(ctx) > 0
    else:
        e2e = out["end_to_end"]
        assert e2e["serve_tokens_per_s"] > 0
        assert e2e["ttft_p95_ms"] >= out["timings"]["ttft_median_ms"] > 0
        assert e2e["itl_p95_ms"] >= out["timings"]["itl_median_ms"] >= 0
        for name in ("decode_step_ms", "prefill_step_ms", "prefill_step_share",
                     "preemptions", "compiles_in_window", "ttft_median_ms"):
            assert _load("layer_metrics", name).compute(ctx) is not None
        occupancy = _load("layer_metrics", "batch_occupancy").compute(ctx)
        assert 0 < occupancy <= 100


def test_cli_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "TPU" in proc.stderr
