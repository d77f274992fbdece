"""What PR 32 adds to the benchmark: the lfm2_moe configuration against
the catalog row it was cut from, its traffic mix and cell, the two new
readers on hand-made counters and events, and kind `serve_family` for a
second at toy widths on the CPU over a model with a state group.  Nothing
here is a measurement."""
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

from benchmark.lib import family, lfm2_ops  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "lfm2-24b-a2b-l9.agents-c64"
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(BENCH, "configs", "lfm2-24b-a2b-l9.json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "agents-c64.json")) as _f:
    TRAFFIC = json.load(_f)
with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_slice.json")) as _f:
    SLICE = json.load(_f)
NOT_PUBLISHED = ("source", "reduced", "published", "deployment", "assumed",
                 "harness", "initializer_range", "head_dim",
                 "tie_word_embeddings")


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
                    if r["name"] == "LFM2-24B-A2B")


# -- the configuration ---------------------------------------------------------

def test_manifest_source_is_the_catalogs():
    entry, = [c for c in MANIFEST["configs"]
              if c["name"] == "lfm2-24b-a2b-l9"]
    assert entry["source"] == _catalog_row()["source_url"]
    assert entry["reduced"] == CONFIG["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers"]
    assert MANIFEST["configs"][-1] is entry          # appended, not inserted


@pytest.mark.parametrize("key", sorted(k for k in CONFIG
                                       if k not in NOT_PUBLISHED))
def test_config_key_equals_the_catalog_rows(key):
    """Every key of the published config, under its own name and value
    (nested groups whole), but for `reduced`; a reduced key states its
    published value."""
    published = _catalog_row()["config"]
    assert key in published
    if key in CONFIG["reduced"]:
        assert CONFIG[key] != published[key]
        assert CONFIG["published"][key] == published[key] \
            or key == "layer_types"
    else:
        assert CONFIG[key] == published[key]


def test_config_leaves_no_published_key_out_and_cuts_no_width():
    published = _catalog_row()["config"]
    assert set(published) <= set(CONFIG)
    assert set(NOT_PUBLISHED) - {"source", "reduced", "published",
                                 "deployment", "harness"} \
        <= set(CONFIG["assumed"]) | {"assumed"}
    # depth alone is cut: layer 0, then two whole periods (layers 2-9)
    kinds = published["layer_types"]
    assert CONFIG["layer_types"] == kinds[:1] + kinds[2:10]
    assert CONFIG["num_hidden_layers"] == len(CONFIG["layer_types"]) == 9
    assert (CONFIG["num_experts"], CONFIG["vocab_size"]) == (64, 65536)
    assert CONFIG["harness"]["kwargs"] == {"router_experts": 64,
                                           "first_expert": 0}
    assert CONFIG["head_dim"] * CONFIG["num_attention_heads"] \
        == CONFIG["hidden_size"]
    # the floors: a whole period and four layers after the dense one
    assert CONFIG["num_hidden_layers"] - CONFIG["num_dense_layers"] >= 4


def test_family_builds_the_configuration_as_it_is_run():
    cfg = family.model_config(CONFIG)
    assert (cfg.num_experts, cfg.router_experts, cfg.first_expert) \
        == (64, 64, 0)
    assert cfg.layer_types == CONFIG["layer_types"]
    assert cfg.rope_theta == 1e6 and cfg.head_dim == 64
    assert cfg.num_hidden_layers == 9 and cfg.conv_L_cache == 3


def test_weight_rule_suits_every_parameter_name():
    """`family.weight_rule` goes by the name: norm scales 1, the selection
    bias 0, every matrix, the embedding and the convolution's taps drawn."""
    from paddle_tpu.models import Lfm2MoeForCausalLM, lfm2_test_config

    model = Lfm2MoeForCausalLM(lfm2_test_config())
    rules = {n.rstrip("0123456789").rstrip("_"): family.weight_rule(n)
             for n, _ in model.named_parameters()}
    assert {n for n, r in rules.items() if r == "ones"} == {
        "embedding_norm", "operator_norm", "ffn_norm", "q_norm", "k_norm"}
    assert [n for n, r in rules.items() if r == "zeros"] == ["expert_bias"]
    assert {"embed", "conv_in_w", "conv_w", "conv_out_w", "router_w",
            "exp_down_w"} <= {n for n, r in rules.items() if r == "normal"}


def test_cell_traffic_is_the_issues():
    assert TRAFFIC["kind"] == "serve_family" and TRAFFIC["clients"] == 64
    assert TRAFFIC["prompt_len"] == [[256, 4], [1024, 4], [4096, 2]]
    assert TRAFFIC["max_tokens"] == [[128, 2], [256, 2], [512, 1]]
    assert TRAFFIC["engine"] == {"block_size": 64, "max_num_seqs": 64,
                                 "max_model_len": 4608}
    assert (TRAFFIC["warmup_s"], TRAFFIC["trace_s"],
            TRAFFIC["check_requests"]) == (8, 2, 4)
    cell = MANIFEST["workloads"][-1]
    assert cell["name"] == CELL and cell["chips"] == 1
    assert (cell["config"], cell["traffic"]) == ("lfm2-24b-a2b-l9",
                                                 "agents-c64")
    # every request fits the model length the engine is built for
    from benchmark.lib.traffic import Requests
    deck = Requests(TRAFFIC, CONFIG["vocab_size"], 1)
    assert max(p + o for p, o in deck.pairs) <= 4608


# -- the readers ---------------------------------------------------------------

COUNTERS = {
    "serving/step_time{phase=decode}:count": 100,
    # a step: 64 rows of 2,000 live keys in the two attention layers
    "serving/kv_tokens_live{group=full}": 100 * 128000,
    # a step: 60 of 64 experts touched in each of the 8 expert layers
    "serving/moe_experts_touched{phase=decode}": 100 * 8 * 60,
    "serving/moe_experts_touched{phase=prefill}": 99999,
    "serving/moe_pairs{phase=decode,where=held}": 100 * 8 * 256,
}
_KERNEL = ('%ragged_paged_attention.{} = (bf16[64,8,512]) custom-call(), '
           'custom_call_target="tpu_custom_call"')
_PRODUCT = ('%ragged-dot-none.{} = {} custom-call(bf16[{},2048] %x), '
            'custom_call_target="tpu_custom_call"')
# three decode steps in the slice: six kernel calls of 0.5 ms; per step 12
# ms of the decode program's products (256 rows); a prefill's (1,024 and
# 4,096 rows) and the metadata call are not the decode program's
EVENTS = {"devices": {"/device:TPU:0": [
    [_KERNEL.format(i), i * 20000000, 500000] for i in range(6)] + [
    [_PRODUCT.format(i, shape, 256), 1000000 + i * 20000000, dur]
    for i in range(3) for shape, dur in (("bf16[256,1536]{1,0}", 8000000),
                                         ("f32[256,2048]{1,0}", 4000000))] + [
    [_PRODUCT.format(7, "bf16[1024,1536]{1,0}", 1024), 70000000, 9000000],
    [_PRODUCT.format(8, "f32[4096,2048]{1,0}", 4096), 80000000, 9000000],
    ["%ragged-dot-metadata = (s32[65]) custom-call()", 90000000, 100000],
    ["%fusion.1 = bf16[256,1536]{1,0} fusion(%ragged-dot-none.1)",
     91000000, 4000000]]}, "host": []}
CTX = {"counters": COUNTERS, "config": CONFIG, "traffic": TRAFFIC,
       "events": EVENTS, "peaks": {"hbm_bytes_per_s": 819e9}, "timings": {}}
WANT = {
    # 128,000 keys x 2 layers x 2 KB over 819 GB/s, over 2 x 0.5 ms
    "ragged_gqa64_roofline":
        100 * (128000 * 2 * 2048 / 819e9) / (2 * 0.5e-3),
    # 480 experts x 18.9 MB over 819 GB/s, over 12 ms
    "expert_products_roofline":
        100 * (480 * 3 * 2048 * 1536 * 2 / 819e9) / 12e-3,
}


@pytest.mark.parametrize("stem", sorted(WANT))
def test_reader_on_hand_made_numbers(stem):
    got = _load("layer_metrics", stem).compute(CTX)
    assert got == pytest.approx(WANT[stem]) and 0 < got < 100


@pytest.mark.parametrize("stem", sorted(WANT))
def test_reader_finds_nothing_on_another_program(stem):
    """The recorded slice of a GPT training step, a program without the
    counters, a configuration of another family: None, never a raise (the
    driver runs the readers over the parent too)."""
    reader = _load("layer_metrics", stem)
    ctx = {**CTX, "events": SLICE}
    assert reader.compute(ctx) is None
    assert reader.compute({**CTX, "counters": {}}) is None
    assert reader.compute({**CTX, "counters": {
        "serving/step_time{phase=decode}:count": 5}}) is None
    gpt = {"num_hidden_layers": 24, "harness": {"dtype": "bfloat16"}}
    assert reader.compute({**CTX, "config": gpt}) is None


@pytest.mark.parametrize("stem,layer", [
    ("ragged_gqa64_roofline", "kernels"),
    ("expert_products_roofline", "expert layer")])
def test_manifest_entry_of(stem, layer):
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == stem + ".serve"]
    assert entry == {"name": stem + ".serve", "unit": "%",
                     "better": "higher", "source": "device_trace",
                     "layer": layer, "moves": "serve_tokens_per_s",
                     "workloads": [CELL]}
    assert entry in MANIFEST["per_layer"][-2:]        # appended


def test_cell_joins_the_lists_the_issue_names():
    joined = {"device_idle_share.serve", "pallas_time_share.serve",
              "decode_step_ms.serve", "prefill_step_ms.serve",
              "prefill_step_share.serve", "ttft_median_ms.serve",
              "itl_median_ms.serve", "batch_occupancy.serve",
              "preemptions.serve", "ttft_p95_ms.unbounded",
              "itl_p95_ms.unbounded", "compiles_in_window.serve",
              "moe_time_share.serve", "expert_tokens_per_step.serve",
              "ragged_gqa64_roofline.serve",
              "expert_products_roofline.serve"}
    has = {m["name"] for m in MANIFEST["per_layer"]
           if CELL in m.get("workloads", ())}
    assert has == joined
    for m in MANIFEST["per_layer"]:
        if m["name"] in joined:
            assert m["workloads"][-1] == CELL            # appended
    e2e, = [m for m in MANIFEST["end_to_end"]
            if m["name"] == "serve_tokens_per_s"]
    assert e2e["workloads"][-1] == CELL


def test_bytes_and_names_from_shapes():
    assert lfm2_ops.attention_layers(CONFIG) == 2
    assert lfm2_ops.kv_bytes_per_token_layer(CONFIG) == 2048
    assert lfm2_ops.expert_weight_bytes(CONFIG) == 3 * 2048 * 1536 * 2
    assert lfm2_ops.decode_product_rows(CONFIG, TRAFFIC) == 256
    assert lfm2_ops.decode_kv_bytes(CONFIG, COUNTERS) \
        == 100 * 128000 * 2 * 2048
    assert lfm2_ops.decode_kv_bytes(CONFIG, {}) is None
    names = [e[0] for e in EVENTS["devices"]["/device:TPU:0"]]
    assert sum(lfm2_ops.is_decode_grouped_product(n, 256)
               for n in names) == 6
    assert sum(lfm2_ops.is_decode_grouped_product(n, 1024)
               for n in names) == 1
    # a tuple result counts by any member; an operand's shape does not
    assert lfm2_ops.is_decode_grouped_product(
        "%ragged-dot-none.2 = (f32[256,2048]{1,0}, s32[8]) custom-call()",
        256)
    assert not lfm2_ops.is_decode_grouped_product(
        "%ragged-dot-none.3 = f32[512,2048]{1,0} custom-call(bf16[256,8] "
        "%a)", 256)


# -- the runner, a second on the CPU -------------------------------------------

TINY = {"vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 5,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "num_experts": 8, "num_experts_per_tok": 2, "num_dense_layers": 1,
        "conv_L_cache": 3, "norm_eps": 1e-5, "routed_scaling_factor": 1,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "max_position_embeddings": 256, "initializer_range": 0.02,
        "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
        "harness": {"constructor": "paddle_tpu.models:Lfm2MoeConfig",
                    "model": "paddle_tpu.models:Lfm2MoeForCausalLM",
                    "reference": "benchmark.lib.reference_lfm2",
                    "kwargs": {"router_experts": 8, "first_expert": 0},
                    "dtype": "float32"}}
TINY_TRAFFIC = {"kind": "serve_family", "clients": 3,
                "prompt_len": [[16, 2], [48, 1]],
                "max_tokens": [[4, 1], [8, 1]],
                "engine": {"block_size": 8, "max_num_seqs": 4,
                           "max_model_len": 64},
                "warmup_s": 0.5, "trace_s": 0.5, "check_requests": 4}


def test_runner_one_second_on_cpu(monkeypatch):
    """Kind `serve_family`, unedited, over a model with a state group:
    every check but the kernels' (no Pallas on a CPU), all routed pairs
    counted, 64 of 64... here 8 of 8 experts held."""
    from benchmark.lib.common import CompileCounter

    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    kind = _load("kinds", "serve_family")
    out = kind.run({
        "cell": {"name": "tiny"}, "config": TINY, "traffic": TINY_TRAFFIC,
        "seed": 2 ** 31 + 11, "seconds": 1.0, "trace": False,
        "t0": time.perf_counter(), "compiles": CompileCounter()})
    checks = dict(out["checks"])
    assert checks.pop("kernel_paths") is False
    assert all(checks.values()), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["timings"]["kv_pool_blocks"] == {"full": 64}
    c = out["counters"]
    assert c["serving/state_slot_steps{group=conv}"] > 0
    assert c.get("serving/moe_pairs{phase=decode,where=absent}", 0) == 0
    ctx = {"counters": c, "timings": out["timings"],
           "end_to_end": out["end_to_end"], "config": TINY,
           "traffic": TINY_TRAFFIC, "events": {"devices": {}, "host": []},
           "peaks": {"hbm_bytes_per_s": 819e9}}
    per_step = _load("layer_metrics", "expert_tokens_per_step").compute(ctx)
    assert 0 < per_step <= 4 * 2 / 8
    for stem in WANT:                 # no device event: nothing to read
        assert _load("layer_metrics", stem).compute(ctx) is None
