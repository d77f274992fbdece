"""What PR 34 adds under `benchmark/`: the mistral4 configuration against
the catalog row it was cut from, its traffic mix, the new readers on
hand-made counters and events, and kind `serve_family` for a second at toy
widths on the CPU over a model with a latent group.  The manifest is
pinned by MEMBERSHIP (`name in list`), never by position, so the next cell
appended turns nothing here red.  Nothing here is a measurement."""
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

from benchmark.lib import family, mla_ops  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "mistral-small-4-ep8-l8"
with open(os.path.join(BENCH, "configs", NAME + ".json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "longctx-c64.json")) as _f:
    TRAFFIC = json.load(_f)
with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_slice.json")) as _f:
    SLICE = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELL = NAME + ".longctx-c64"
NOT_PUBLISHED = ("source", "reduced", "published", "deployment", "assumed",
                 "harness", "initializer_range")
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
# what `reduced` may never name (the builder's contract): a width
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "head_dim", "q_lora_rank", "kv_lora_rank", "qk_head_dim",
          "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "num_experts_per_tok", "num_attention_heads")


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
                    if r["name"] == "Mistral-Small-4-119B-2603")


# -- the configuration ---------------------------------------------------------

def test_source_is_the_catalogs():
    assert CONFIG["source"].startswith(_catalog_row()["source_url"])
    assert CONFIG["reduced"] == REDUCED


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
        assert CONFIG["published"][key] == published[key]
    else:
        assert CONFIG[key] == published[key]


def test_config_leaves_no_published_key_out_and_cuts_no_width():
    published = _catalog_row()["config"]
    assert set(published) <= set(CONFIG)
    assert not set(REDUCED) & set(WIDTHS)
    for key in WIDTHS:
        assert CONFIG[key] == published[key], key
    assert (CONFIG["num_hidden_layers"], CONFIG["n_routed_experts"],
            CONFIG["vocab_size"]) == (8, 16, 16384)
    assert CONFIG["harness"]["kwargs"] == {"router_experts": 128,
                                           "first_expert": 0}
    # the floors: four layers, 8 routed experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] >= 4
    assert CONFIG["n_routed_experts"] >= 8
    assert CONFIG["vocab_size"] * 8 >= published["vocab_size"]
    # 8 chips share a layer: an eighth of the experts and of the vocabulary
    assert CONFIG["n_routed_experts"] * 8 == published["n_routed_experts"]
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert "8 chips share each layer" in CONFIG["deployment"]
    for said in ("score function", "expert_bias", "softmax scale",
                 "query scale", "initializer_range", "norm weights",
                 "vision tower"):
        assert said in CONFIG["assumed"], said
    assert "softmax" in CONFIG["assumed"]["score function"]


def test_family_builds_the_configuration_as_it_is_run():
    cfg = family.model_config(CONFIG)
    assert (cfg.n_routed_experts, cfg.router_experts, cfg.first_expert) \
        == (16, 128, 0)
    assert (cfg.num_hidden_layers, cfg.vocab_size) == (8, 16384)
    assert (cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.q_lora_rank) \
        == (256, 64, 1024)
    assert cfg.rope_parameters == CONFIG["rope_parameters"]
    assert cfg.softmax_scale == pytest.approx(
        128 ** -0.5 * (0.1 * 4.852030263919617 + 1) ** 2)
    assert cfg.num_experts_per_tok == 4      # what `_pairs_dropped` reads


def test_parameter_count_is_the_deployments():
    """The arithmetic the configuration file states, from the shapes."""
    h, e_w = CONFIG["hidden_size"], CONFIG["moe_intermediate_size"]
    heads = CONFIG["num_attention_heads"]
    outside = (h * CONFIG["q_lora_rank"]
               + CONFIG["q_lora_rank"] * heads * CONFIG["qk_head_dim"]
               + h * (CONFIG["kv_lora_rank"] + CONFIG["qk_rope_head_dim"])
               + CONFIG["kv_lora_rank"] * heads * (
                   CONFIG["qk_nope_head_dim"] + CONFIG["v_head_dim"])
               + heads * CONFIG["v_head_dim"] * h
               + 3 * h * e_w + h * 128)
    assert round(outside / 1e6, 2) == 53.74
    layer = outside + CONFIG["n_routed_experts"] * 3 * h * e_w
    total = CONFIG["num_hidden_layers"] * layer \
        + 2 * CONFIG["vocab_size"] * h
    assert round(total / 1e9, 3) == 3.785
    assert "3.785 B" in CONFIG["deployment"]


def test_weight_rule_suits_every_parameter_name():
    """`family.weight_rule` goes by the name: norm scales 1, the selection
    bias 0, every matrix and the embedding drawn."""
    from paddle_tpu.models import Mistral4ForCausalLM, mistral4_test_config

    model = Mistral4ForCausalLM(mistral4_test_config())
    rules = {n.rstrip("0123456789").rstrip("_"): family.weight_rule(n)
             for n, _ in model.named_parameters()}
    assert {n for n, r in rules.items() if r == "ones"} == {
        "final_norm", "in_norm", "ffn_norm", "q_a_norm", "kv_a_norm"}
    assert [n for n, r in rules.items() if r == "zeros"] == ["expert_bias"]
    assert {"embed", "head", "q_a_w", "q_b_w", "kv_a_w", "kv_b_w", "o_w",
            "router_w", "exp_down_w", "shared_up_w"} \
        <= {n for n, r in rules.items() if r == "normal"}


def test_cell_traffic_is_the_issues():
    assert TRAFFIC["kind"] == "serve_family" and TRAFFIC["clients"] == 64
    assert TRAFFIC["prompt_len"] == [[4096, 4], [8192, 4], [16384, 2]]
    assert TRAFFIC["max_tokens"] == [[256, 2], [512, 2], [1024, 1]]
    assert {k: TRAFFIC["engine"][k] for k in (
        "block_size", "max_num_seqs", "max_model_len")} == {
        "block_size": 64, "max_num_seqs": 64, "max_model_len": 17408}
    assert 10240 <= TRAFFIC["engine"]["num_blocks"] <= 12288
    assert set(TRAFFIC["engine"]) == {"block_size", "max_num_seqs",
                                      "max_model_len", "num_blocks"}
    assert (TRAFFIC["warmup_s"], TRAFFIC["trace_s"],
            TRAFFIC["check_requests"]) == (8, 2, 4)
    # every request fits the model length the engine is built for, and the
    # mean live context fits the pool three standard deviations over
    from benchmark.lib.traffic import Requests
    deck = Requests(TRAFFIC, CONFIG["vocab_size"], 1)
    assert max(p + o for p, o in deck.pairs) <= 17408
    prompts = [v for v, n in TRAFFIC["prompt_len"] for _ in range(n)]
    answers = [v for v, n in TRAFFIC["max_tokens"] for _ in range(n)]
    assert sum(prompts) / len(prompts) == 8192
    assert sum(answers) / len(answers) == 512
    assert TRAFFIC["engine"]["num_blocks"] * 64 >= 650_000


# -- the readers ---------------------------------------------------------------

# -- the manifest, by membership ------------------------------------------------

JOINS = ("serve_tokens_per_s", "device_idle_share.serve",
         "pallas_time_share.serve", "decode_step_ms.serve",
         "prefill_step_ms.serve", "prefill_step_share.serve",
         "ttft_median_ms.serve", "itl_median_ms.serve",
         "batch_occupancy.serve", "preemptions.serve",
         "ttft_p95_ms.unbounded", "itl_p95_ms.unbounded",
         "compiles_in_window.serve", "moe_time_share.serve")
OWN = ("mla_decode_roofline.serve", "mla_kernel_time_share.serve",
       "held_expert_tokens_per_step.serve")
# ISSUE 34: the reader indexes keys this configuration lacks; the host-phase
# lists are pinned to the GPT cells by a test this PR may not edit
STAYS_OUT = ("expert_tokens_per_step.serve", "host_gap_share.serve",
             "schedule_ms.serve", "prepare_ms.serve", "emit_ms.serve",
             "pump_ms.serve", "submit_wait_ms.serve", "queue_wait_ms.serve",
             "kv_window_held_share.serve",
             "ragged_paged_attention_roofline.serve",
             "ragged_gqa64_roofline.serve", "expert_products_roofline.serve")


def _metric(name):
    found = [m for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
             if m["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_manifest_lists_the_configuration_and_one_cell_on_one_chip():
    config = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    assert len(config) == 1
    assert config[0]["file"] == f"benchmark/configs/{NAME}.json"
    assert config[0]["source"] == CONFIG["source"].split(" ")[0]
    assert config[0]["reduced"] == REDUCED == CONFIG["reduced"]
    cells = [w for w in MANIFEST["workloads"] if w["config"] == NAME]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "longctx-c64", 1)]
    assert not any(w["chips"] == 4 for w in MANIFEST["workloads"])


@pytest.mark.parametrize("name", JOINS)
def test_cell_joins_the_list_the_issue_names(name):
    assert CELL in _metric(name)["workloads"]


@pytest.mark.parametrize("name", OWN)
def test_new_metric_lists_this_cell_alone(name):
    m = _metric(name)
    assert m["workloads"] == [CELL]
    assert m["moves"] == "serve_tokens_per_s"
    assert os.path.exists(os.path.join(
        BENCH, "layer_metrics", name.split(".")[0] + ".py"))


@pytest.mark.parametrize("name", STAYS_OUT)
def test_cell_stays_off_the_lists_it_cannot_report(name):
    assert CELL not in _metric(name)["workloads"]


COUNTERS = {
    "serving/step_time{phase=decode}:count": 100,
    # a step: 64 rows of 8,000 live latents
    "serving/kv_tokens_live{group=latent}": 100 * 512000,
}
_KERNEL = ('%ragged_latent_attention.{} = (bf16[64,32,256]) custom-call(), '
           'custom_call_target="tpu_custom_call"')
# three decode steps in the slice: 24 kernel calls of 1 ms; 36 ms of other
# operations, one of them another Mosaic kernel
EVENTS = {"devices": {"/device:TPU:0": [
    [_KERNEL.format(i), i * 2000000, 1000000] for i in range(24)] + [
    ['%flash_fwd.1 = bf16[32,4096,128] custom-call(), '
     'custom_call_target="tpu_custom_call"', 60000000, 6000000],
    ["%fusion.9 = bf16[64,4096]{1,0} fusion(%p)", 70000000, 30000000]]},
    "host": []}
CTX = {"counters": COUNTERS, "config": CONFIG, "traffic": TRAFFIC,
       "events": EVENTS, "timings": {},
       "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
WANT = {
    # 512,000 rows x 8 layers x 640 B over 819 GB/s (the bytes set it:
    # 36,864 FLOP a row over 197 TFLOP/s is a quarter of that), over 8 x 1 ms
    "mla_decode_roofline": 100 * (512000 * 8 * 640 / 819e9) / 8e-3,
    "mla_kernel_time_share": 100 * 24 / 60,
}


@pytest.mark.parametrize("stem", sorted(WANT))
def test_reader_on_hand_made_numbers(stem):
    got = _load("layer_metrics", stem).compute(CTX)
    assert got == pytest.approx(WANT[stem]) and 0 < got < 100


def test_roofline_takes_the_larger_of_bytes_and_operations():
    """On a chip whose MXU is the slower side for this kernel, the
    operations set the least time."""
    slow = {**CTX, "peaks": {"hbm_bytes_per_s": 819e9,
                             "bf16_flops_per_s": 197e11}}
    got = _load("layer_metrics", "mla_decode_roofline").compute(slow)
    assert got == pytest.approx(
        100 * (512000 * 8 * 36864 / 197e11) / 8e-3)


@pytest.mark.parametrize("stem", sorted(WANT))
def test_reader_finds_nothing_on_another_program(stem):
    """The recorded slice of a GPT training step, a program without the
    counters, a configuration of another family: None, never a raise (the
    driver runs the readers over the parent too)."""
    reader = _load("layer_metrics", stem)
    assert reader.compute({**CTX, "events": SLICE}) is None
    if stem == "mla_decode_roofline":
        assert reader.compute({**CTX, "counters": {}}) is None
        assert reader.compute({**CTX, "counters": {
            "serving/step_time{phase=decode}:count": 5}}) is None
        gpt = {"num_hidden_layers": 24, "harness": {"dtype": "bfloat16"}}
        assert reader.compute({**CTX, "config": gpt}) is None
        with open(os.path.join(BENCH, "configs",
                               "lfm2-24b-a2b-l9.json")) as f:
            assert reader.compute({**CTX, "config": json.load(f)}) is None
    other = {"devices": {"/device:TPU:0": [
        ['%ragged_paged_attention.3 = (bf16[64,8,512]) custom-call(), '
         'custom_call_target="tpu_custom_call"', 0, 500000]]}, "host": []}
    assert reader.compute({**CTX, "events": other}) is None


def test_tokens_an_expert_a_step_from_the_pairs_counted():
    """64 rows x top-4 over a router of 128, 16 held: 2.0 a step a layer
    an expert; a family that names its experts otherwise reads None."""
    reader = _load("layer_metrics", "held_expert_tokens_per_step")
    c = {"serving/step_time{phase=decode}:count": 100,
         "serving/moe_pairs{phase=decode,where=held}": 100 * 8 * 32}
    assert reader.compute({"counters": c, "config": CONFIG}) == 2.0
    assert reader.compute({"counters": {}, "config": CONFIG}) is None
    assert reader.compute({"counters": {
        "serving/step_time{phase=decode}:count": 5},
        "config": CONFIG}) is None
    for other in ("lfm2-24b-a2b-l9", "trinity-large-ep8-l5", "gpt3-1.3b"):
        with open(os.path.join(BENCH, "configs", other + ".json")) as f:
            assert reader.compute({"counters": c,
                                   "config": json.load(f)}) is None


def test_bytes_and_operations_from_the_published_shapes():
    assert mla_ops.latent_layers(CONFIG) == 8
    assert mla_ops.latent_bytes_per_token_layer(CONFIG) == 640
    assert mla_ops.latent_flops_per_token_layer(CONFIG) == 36864
    assert mla_ops.decode_latent_tokens(COUNTERS) == 100 * 512000
    assert mla_ops.decode_latent_tokens({}) is None
    gpt = {"num_hidden_layers": 24, "harness": {"dtype": "bfloat16"}}
    assert mla_ops.latent_bytes_per_token_layer(gpt) is None
    assert mla_ops.latent_flops_per_token_layer(gpt) is None
    # the kernel's name in a trace is the program's `pallas_call` name
    import inspect

    from paddle_tpu.ops import ragged_paged_attention as rp
    assert f'name="{mla_ops.LATENT_KERNEL}"' in inspect.getsource(
        rp._latent_kernel_call)


# -- the runner, a second on the CPU -------------------------------------------

TINY = {"vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "q_lora_rank": 32, "kv_lora_rank": 24, "qk_head_dim": 16,
        "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "rms_norm_eps": 1e-6,
        "routed_scaling_factor": 1, "max_position_embeddings": 256,
        "initializer_range": 0.02,
        "rope_parameters": {
            "rope_type": "yarn", "type": "yarn", "rope_theta": 10000,
            "factor": 8, "original_max_position_embeddings": 32,
            "beta_fast": 32, "beta_slow": 1, "mscale": 1,
            "mscale_all_dim": 1, "llama_4_scaling_beta": 0.1},
        "harness": {"constructor": "paddle_tpu.models:Mistral4Config",
                    "model": "paddle_tpu.models:Mistral4ForCausalLM",
                    "reference": "benchmark.lib.reference_mistral4",
                    "kwargs": {"router_experts": 8, "first_expert": 0},
                    "dtype": "float32"}}
TINY_TRAFFIC = {"kind": "serve_family", "clients": 3,
                "prompt_len": [[16, 2], [48, 1]],
                "max_tokens": [[4, 1], [8, 1]],
                "engine": {"block_size": 8, "max_num_seqs": 4,
                           "max_model_len": 64, "num_blocks": 40},
                "warmup_s": 0.5, "trace_s": 0.5, "check_requests": 4}


def test_runner_one_second_on_cpu(monkeypatch):
    """Kind `serve_family`, unedited, over a model with a latent group, 4
    of a router's 8 experts held: every check but the kernels' (no Pallas
    on a CPU), every routed pair counted as held here or absent, the
    reference check over positions past the shrunk original context."""
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
    assert out["timings"]["kv_pool_blocks"] == {"latent": 40}
    c = out["counters"]
    assert c["serving/kv_tokens_live{group=latent}"] > 0
    assert c["serving/kv_block_steps{group=latent}"] > 0
    assert c.get("serving/moe_pairs{phase=decode,where=absent}", 0) > 0
    ctx = {"counters": c, "timings": out["timings"],
           "end_to_end": out["end_to_end"], "config": TINY,
           "traffic": TINY_TRAFFIC, "events": {"devices": {}, "host": []},
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    for stem in WANT:                 # no device event: nothing to read
        assert _load("layer_metrics", stem).compute(ctx) is None
    for stem in ("decode_step_ms", "prefill_step_share", "batch_occupancy",
                 "preemptions"):
        assert _load("layer_metrics", stem).compute(ctx) is not None
    # 4 of the router's 8 experts held, top-2: about 1 pair a token a layer
    per_expert = _load("layer_metrics",
                       "held_expert_tokens_per_step").compute(ctx)
    assert 0 < per_expert <= TINY_TRAFFIC["engine"]["max_num_seqs"]
