"""Kind `serve_family` and what PR 28 adds to the benchmark: the afmoe
configuration against the catalog row it was cut from, the family builder,
the four new readers on hand-made counters and on a recorded slice, and the
runner for a second at toy widths on the CPU.  Nothing here is a
measurement."""
import importlib.util
import json
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import afmoe_ops, family  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "trinity-large-ep8-l5.longmix-c32"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "configs", "trinity-large-ep8-l5.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "longmix-c32.json")) as _f:
    TRAFFIC = json.load(_f)
with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_slice.json")) as _f:
    SLICE = json.load(_f)


def _load(directory, name):
    path = os.path.join(BENCH, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"_bench_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _catalog_row():
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        return next(r for r in map(json.loads, f)
                    if r["name"] == "Trinity-Large-Preview")


def test_manifest_source_is_the_catalogs():
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == "trinity-large-ep8-l5"]
    assert entry["source"] == _catalog_row()["source_url"]
    assert entry["reduced"] == CONFIG["reduced"]


@pytest.mark.parametrize("key", sorted(
    k for k in CONFIG if k not in ("source", "reduced", "published",
                                   "deployment", "assumed", "harness",
                                   "initializer_range")))
def test_config_key_equals_the_catalog_rows(key):
    """Every key of the published config, under its own name and value,
    but for `reduced`; and a reduced key states its published value."""
    published = _catalog_row()["config"]
    assert key in published
    if key in CONFIG["reduced"]:
        assert CONFIG[key] != published[key]
        want = CONFIG["published"][key]
        assert want == published[key] or key == "layer_types"
    else:
        assert CONFIG[key] == published[key]


def test_config_leaves_no_published_key_out_and_cuts_no_width():
    published = _catalog_row()["config"]
    assert set(published) <= set(CONFIG)
    widths = ("hidden_size", "intermediate_size", "moe_intermediate_size",
              "head_dim", "num_experts_per_tok", "sliding_window")
    assert not set(widths) & set(CONFIG["reduced"])
    assert CONFIG["layer_types"] == published["layer_types"][:5]
    assert CONFIG["harness"]["kwargs"]["router_experts"] \
        == published["num_experts"]
    # the floors: a whole period and four layers after the dense one, at
    # least 8 experts, at least an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] - CONFIG["num_dense_layers"] >= 4
    assert CONFIG["num_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]


def test_cell_traffic_is_the_issues():
    assert TRAFFIC["kind"] == "serve_family" and TRAFFIC["clients"] == 32
    assert TRAFFIC["prompt_len"] == [[1024, 4], [4096, 4], [8192, 2]]
    assert TRAFFIC["max_tokens"] == [[128, 3], [256, 2]]
    assert TRAFFIC["engine"] == {"block_size": 64, "max_num_seqs": 32,
                                 "max_model_len": 8448}
    assert (TRAFFIC["warmup_s"], TRAFFIC["trace_s"],
            TRAFFIC["check_requests"]) == (6, 2, 4)
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert cell["chips"] == 1 and cell["traffic"] == "longmix-c32"


def test_family_builds_the_configuration_as_it_is_run():
    cfg = family.model_config(CONFIG)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert) \
        == (32, 256, 0)
    assert cfg.layer_types == CONFIG["layer_types"]
    assert cfg.vocab_size == 25024 and cfg.sliding_window == 4096
    assert family.weight_rule("post_attn_norm") == "ones"
    assert family.weight_rule("expert_bias") == "zeros"
    assert family.weight_rule("exp_down_w") == "normal"


# -- the readers ---------------------------------------------------------------

COUNTERS = {
    "serving/step_time{phase=decode}:count": 100,
    "serving/step_time{phase=decode}:sum": 4.0,
    # 100 decode steps x 4 expert layers x 32 experts x 0.5 tokens
    "serving/moe_pairs{phase=decode,where=held}": 6400,
    "serving/moe_pairs{phase=prefill,where=held}": 99999,
    "serving/kv_block_steps{group=full}": 8000,
    "serving/kv_block_steps{group=window}": 5000,
    # a step: 100,000 live keys in the full layer, 80,000 in each window one
    "serving/kv_tokens_live{group=full}": 100 * 100000,
    "serving/kv_tokens_live{group=window}": 100 * 80000,
}
# a step's five kernel calls must read (100,000 + 4 x 80,000) x 4 KB =
# 1.72 GB, 2.1 ms at 819 GB/s; the slice's calls take 1 ms each
_KERNEL = ('%ragged_paged_attention.{} = (bf16[192,1,1024]) custom-call(), '
           'custom_call_target="tpu_custom_call"')
# ten kernel calls of 1 ms, 6 ms of grouped products, 4 ms of the rest
EVENTS = {"devices": {"/device:TPU:0": [
    [_KERNEL.format(i), i * 2000000, 1000000] for i in range(10)] + [
    ['%ragged-dot-none.1 = bf16[128,3072]{1,0} custom-call(), '
     'custom_call_target="tpu_custom_call"', 30000000, 5000000],
    ["%ragged-dot-metadata = (s32[33]) custom-call()", 36000000, 1000000],
    ["%fusion.1 = bf16[8]{0} fusion()", 40000000, 4000000]]}, "host": []}
CTX = {"counters": COUNTERS, "config": CONFIG, "events": EVENTS,
       "peaks": {"hbm_bytes_per_s": 819e9}, "timings": {}}
WANT = {"moe_time_share": 30.0, "expert_tokens_per_step": 0.5,
        "kv_window_held_share": 62.5,
        "ragged_paged_attention_roofline":
            100 * (420000 * 4096 / 819e9) / (5 * 1e-3)}


@pytest.mark.parametrize("stem", sorted(WANT))
def test_reader_on_hand_made_numbers(stem):
    assert _load("layer_metrics", stem).compute(CTX) \
        == pytest.approx(WANT[stem])


@pytest.mark.parametrize("stem", sorted(WANT))
def test_reader_finds_nothing_on_another_program(stem):
    """The recorded slice of a GPT training step and a program without
    the counters: None, never a raise (the driver runs the readers over
    the parent too)."""
    gpt = {k: v for k, v in CONFIG.items()}
    ctx = {"counters": {"serving/step_time{phase=decode}:count": 5},
           "config": gpt, "events": SLICE, "peaks": CTX["peaks"],
           "timings": {}}
    assert _load("layer_metrics", stem).compute(ctx) is None
    assert _load("layer_metrics", stem).compute(
        {**ctx, "counters": {}}) is None


@pytest.mark.parametrize("stem", sorted(WANT))
def test_manifest_entry_of(stem):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == stem + ".serve"]
    assert entry["workloads"] == [CELL]
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["layer"] in ("expert layer", "KV cache", "kernels")
    if "roofline" in stem:
        assert entry["unit"] == "%" and entry["better"] == "higher"


def test_bytes_and_names_from_shapes():
    assert afmoe_ops.kv_bytes_per_token_layer(CONFIG) == 4096
    assert afmoe_ops.layers_by_group(CONFIG) == {"full": 1, "window": 4}
    assert afmoe_ops.decode_kv_bytes(CONFIG, COUNTERS) \
        == 100 * 420000 * 4096
    assert afmoe_ops.decode_kv_bytes(CONFIG, {}) is None
    assert afmoe_ops.kernel_call_seconds(EVENTS, "ragged_paged_attention") \
        == (pytest.approx(0.01), 10)
    assert afmoe_ops.kernel_call_seconds(SLICE, "ragged_paged_attention") \
        == (0.0, 0)
    assert afmoe_ops.is_grouped_product(EVENTS["devices"][
        "/device:TPU:0"][10][0])
    assert not afmoe_ops.is_grouped_product(
        "%fusion.2 = bf16[8] fusion(bf16[8] %ragged-dot-none.1)")


def test_new_cell_reports_what_the_serving_cells_report():
    """Every `.serve` / `.unbounded` metric takes the new cell, but the
    seven whose lists `test_host_phase_metrics.test_manifest_entry_of`
    pins to the two GPT cells (PERF.md, section 7 (a))."""
    pinned = {"host_gap_share", "schedule_ms", "prepare_ms", "emit_ms",
              "pump_ms", "submit_wait_ms", "queue_wait_ms"}
    for m in MANIFEST["per_layer"]:
        stem, _, suffix = m["name"].partition(".")
        if suffix in ("serve", "unbounded"):
            assert (CELL in m["workloads"]) == (stem not in pinned), m
    e2e, = [m for m in MANIFEST["end_to_end"]
            if m["name"] == "serve_tokens_per_s"]
    assert CELL in e2e["workloads"]


# -- the runner, a second on the CPU -------------------------------------------

TINY = {"vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 5,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_experts": 4, "num_experts_per_tok": 2, "num_dense_layers": 1,
        "num_shared_experts": 1, "sliding_window": 16,
        "global_attn_every_n_layers": 4, "rope_theta": 10000,
        "rms_norm_eps": 1e-5, "route_scale": 2.448,
        "max_position_embeddings": 256, "initializer_range": 0.02,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention",
                                                    "sliding_attention"],
        "harness": {"constructor": "paddle_tpu.models:AfmoeConfig",
                    "model": "paddle_tpu.models:AfmoeForCausalLM",
                    "reference": "benchmark.lib.reference_afmoe",
                    "kwargs": {"router_experts": 16, "first_expert": 4},
                    "dtype": "float32"}}
TINY_TRAFFIC = {"kind": "serve_family", "clients": 3,
                "prompt_len": [[16, 2], [48, 1]],
                "max_tokens": [[4, 1], [8, 1]],
                "engine": {"block_size": 8, "max_num_seqs": 4,
                           "max_model_len": 64},
                "warmup_s": 0.5, "trace_s": 0.5, "check_requests": 4}


def test_runner_one_second_on_cpu(monkeypatch):
    from benchmark.lib.common import CompileCounter

    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    kind = _load("kinds", "serve_family")
    out = kind.run({
        "cell": {"name": "tiny"}, "config": TINY, "traffic": TINY_TRAFFIC,
        "seed": 2 ** 31 + 11, "seconds": 1.0, "trace": False,
        "t0": time.perf_counter(), "compiles": CompileCounter()})
    checks = dict(out["checks"])
    assert checks.pop("kernel_paths") is False          # no Pallas on a CPU
    assert all(checks.values()), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["end_to_end"]["serve_tokens_per_s"] > 0
    assert out["timings"]["kv_pool_blocks"] == {"full": 64, "window": 12}
    ctx = {"counters": out["counters"], "timings": out["timings"],
           "end_to_end": out["end_to_end"], "config": TINY,
           "events": {"devices": {}, "host": []},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    per_step = _load("layer_metrics", "expert_tokens_per_step").compute(ctx)
    assert 0 < per_step < 4 * 2
    share = _load("layer_metrics", "kv_window_held_share").compute(ctx)
    assert 0 < share <= 100
    assert kind._pairs_dropped(out["counters"],
                               family.model_config(TINY)) == 0
    for name in ("decode_step_ms", "prefill_step_ms", "preemptions",
                 "batch_occupancy"):
        assert _load("layer_metrics", name).compute(ctx) is not None
