"""What PR 38 adds under `benchmark/`: the brumby configuration against the
catalog row it was cut from, its traffic mix, the four new readers on
hand-made counters and events, and what kind `serve_family_paths` decides
from data.  The manifest is pinned by MEMBERSHIP (`name in list`), never
by position or by exact list, so the next cell appended turns nothing here
red.  Nothing here is a measurement."""
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

from benchmark.lib import family, retention_ops  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME = "brumby-14b-l6"
with open(os.path.join(BENCH, "configs", NAME + ".json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "longgen-c24.json")) as _f:
    TRAFFIC = json.load(_f)
with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "trace_slice.json")) as _f:
    SLICE = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELL = NAME + ".longgen-c24"
NOT_PUBLISHED = ("source", "reduced", "published", "deployment", "assumed",
                 "harness", "initializer_range", "retention_degree",
                 "state_dtype")
# what `reduced` may never name (the builder's contract): a width
WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads", "vocab_size")


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
                    if r["name"] == "Brumby-14B-Base")


# -- the configuration ---------------------------------------------------------

def test_source_is_the_catalogs():
    assert CONFIG["source"].startswith(_catalog_row()["source_url"])
    assert CONFIG["reduced"] == ["num_hidden_layers"]


@pytest.mark.parametrize("key", sorted(k for k in CONFIG
                                       if k not in NOT_PUBLISHED))
def test_config_key_equals_the_catalog_rows(key):
    """Every key of the published config, under its own name and value,
    but for `reduced`; the reduced key states its published value."""
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
    assert not set(CONFIG["reduced"]) & set(WIDTHS)
    for key in WIDTHS:
        assert CONFIG[key] == published[key], key
    # depth alone: 6 of 40 (the floor is four; every layer is alike)
    assert CONFIG["num_hidden_layers"] == 6 >= 4
    assert CONFIG["published"] == {"num_hidden_layers": 40}
    for said in ("each layer whole on one chip", "pipeline stages",
                 "6 of 40"):
        assert said in CONFIG["deployment"], said


@pytest.mark.parametrize("line", [
    "retention_degree", "gate", "normalisation", "q_norm, k_norm",
    "rotary positions", "state_dtype", "initializer_range", "norm weights"])
def test_assumed_lists_what_config_json_does_not_carry(line):
    assert line in CONFIG["assumed"]
    assert len(CONFIG["assumed"][line]) > 10


def test_family_builds_the_configuration_as_it_is_run():
    cfg = family.model_config(CONFIG)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.hidden_size) \
        == (6, 151936, 5120)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
            cfg.intermediate_size) == (40, 8, 128, 17408)
    assert (cfg.retention_degree, cfg.state_dtype, cfg.initializer_range) \
        == (2, "float32", 0.02)
    assert not hasattr(cfg, "num_experts_per_tok")
    harness = CONFIG["harness"]
    assert harness["reference"] == "benchmark.lib.reference_brumby"
    assert harness["dtype"] == "bfloat16" and harness["kwargs"] == {}
    assert harness["kernel_paths"] == {
        "required": ["retention_prefill_kernel", "retention_decode_kernel"],
        "allowed_fallbacks": []}


def test_parameter_and_state_bytes_are_the_issues():
    """The arithmetic of ISSUE 38, from the shapes."""
    h, i = CONFIG["hidden_size"], CONFIG["intermediate_size"]
    hq = CONFIG["num_attention_heads"] * CONFIG["head_dim"]
    hkv = CONFIG["num_key_value_heads"] * CONFIG["head_dim"]
    layer = 2 * h * hq + 2 * h * hkv + h * CONFIG["num_key_value_heads"] \
        + 3 * h * i
    assert round(layer / 1e6, 1) == 330.3
    total = CONFIG["num_hidden_layers"] * layer \
        + 2 * CONFIG["vocab_size"] * h
    assert round(2 * total / 1e9, 2) == 7.08      # + the norms' vectors
    assert retention_ops.state_bytes(CONFIG) == 8 * 8256 * 129 * 4
    slots = TRAFFIC["engine"]["max_num_seqs"] + 1
    assert round(slots * 6 * retention_ops.state_bytes(CONFIG) / 1e9, 2) \
        == 5.11


def test_weight_rule_suits_every_parameter_name():
    """`family.weight_rule` goes by the name: norm scales 1, every matrix,
    the embedding, the head and the gate's projection drawn."""
    from paddle_tpu.models import BrumbyForCausalLM, brumby_test_config

    model = BrumbyForCausalLM(brumby_test_config())
    rules = {n.rstrip("0123456789").rstrip("_"): family.weight_rule(n)
             for n, _ in model.named_parameters()}
    assert {n for n, r in rules.items() if r == "ones"} == {
        "final_norm", "in_norm", "post_norm", "q_norm", "k_norm"}
    assert not [n for n, r in rules.items() if r == "zeros"]
    assert {n for n, r in rules.items() if r == "normal"} == {
        "embed", "head", "q_w", "k_w", "v_w", "g_w", "o_w", "mlp_gate_w",
        "mlp_up_w", "mlp_down_w"}


def test_reference_imports_nothing_from_the_program():
    with open(os.path.join(BENCH, "lib", "reference_brumby.py")) as f:
        text = f.read()
    assert "paddle_tpu" not in text.replace("BrumbyForCausalLM", "")
    ref = family.reference(CONFIG)
    assert ref.FAULTS == (None, "float8", "no_gate", "degree_1", "no_carry",
                          "unnormalised")


def test_cell_traffic_is_the_issues():
    assert TRAFFIC["kind"] == "serve_family_paths"
    assert TRAFFIC["clients"] == 24
    assert TRAFFIC["prompt_len"] == [[1024, 4], [4096, 4], [8192, 2]]
    assert TRAFFIC["max_tokens"] == [[512, 2], [1024, 2], [2048, 1]]
    assert TRAFFIC["engine"] == {"block_size": 64, "max_num_seqs": 24,
                                 "max_model_len": 10240}
    assert (TRAFFIC["warmup_s"], TRAFFIC["trace_s"],
            TRAFFIC["check_requests"]) == (8, 2, 4)
    from benchmark.lib.traffic import Requests
    deck = Requests(TRAFFIC, CONFIG["vocab_size"], 1)
    assert max(p + o for p, o in deck.pairs) <= 10240
    prompts = [v for v, n in TRAFFIC["prompt_len"] for _ in range(n)]
    answers = [v for v, n in TRAFFIC["max_tokens"] for _ in range(n)]
    assert round(sum(prompts) / len(prompts)) == 3686
    assert sum(answers) / len(answers) == 1024
    # every prompt is whole chunks of the prefill kernel: the prefill
    # reader's count of positions is exact here
    assert all(p % retention_ops.CHUNK == 0 for p in prompts)


# -- the manifest, by membership ------------------------------------------------

JOINS = ("serve_tokens_per_s", "device_idle_share.serve",
         "pallas_time_share.serve", "decode_step_ms.serve",
         "prefill_step_ms.serve", "prefill_step_share.serve",
         "ttft_median_ms.serve", "itl_median_ms.serve",
         "batch_occupancy.serve", "preemptions.serve",
         "compiles_in_window.serve", "step_host_ms.serve",
         "step_wait_share.serve", "host_cpu_share.serve",
         "pipeline_full_share.serve", "host_stall_s.serve")
OWN = {"retention_decode_roofline.serve": "kernels",
       "retention_prefill_roofline.serve": "kernels",
       "retention_time_share.serve": "kernels",
       "state_bytes_per_decode_step.serve": "KV cache"}
# readers of K/V blocks, experts or another family's kernel find nothing
# in an attention-free dense model; the host-phase lists are the GPT cells'
STAYS_OUT = ("kv_window_held_share.serve", "moe_time_share.serve",
             "expert_tokens_per_step.serve",
             "ragged_paged_attention_roofline.serve",
             "ragged_gqa64_roofline.serve", "mla_decode_roofline.serve",
             "mla_kernel_time_share.serve", "host_gap_share.serve")


def _metric(name):
    found = [m for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]
             if m["name"] == name]
    assert len(found) == 1, name
    return found[0]


def test_manifest_lists_the_configuration_and_one_cell_on_one_chip():
    config = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    assert len(config) == 1
    assert config[0]["file"] == f"benchmark/configs/{NAME}.json"
    assert config[0]["source"] == _catalog_row()["source_url"]
    assert config[0]["reduced"] == CONFIG["reduced"]
    cells = [w for w in MANIFEST["workloads"] if w["config"] == NAME]
    assert [(w["name"], w["traffic"], w["chips"]) for w in cells] == [
        (CELL, "longgen-c24", 1)]
    assert len(cells[0]["why"]) <= 200
    for said in ("6 of 40", "76%", "kv_*"):
        assert said in cells[0]["why"], said


@pytest.mark.parametrize("name", JOINS)
def test_cell_joins_the_list_the_issue_names(name):
    assert CELL in _metric(name)["workloads"]


@pytest.mark.parametrize("name", sorted(OWN))
def test_new_metric_lists_this_cell_alone(name):
    m = _metric(name)
    assert m["workloads"] == [CELL]
    assert m["moves"] == "serve_tokens_per_s" and m["layer"] == OWN[name]
    assert os.path.exists(os.path.join(
        BENCH, "layer_metrics", name.split(".")[0] + ".py"))


@pytest.mark.parametrize("name", STAYS_OUT)
def test_cell_stays_off_the_lists_it_cannot_report(name):
    assert CELL not in _metric(name)["workloads"]


@pytest.mark.parametrize("name", [n for n in JOINS if n.endswith(".serve")
                                  and "device" not in n and "pallas" not in n])
def test_accepted_reader_reads_a_number_in_this_cell(name):
    """The counter and timing readers the cell joins, over the counters an
    attention-free engine writes (no `kv_*`, no `moe_*`): a number, so
    the traced run's line holds the metric."""
    ctx = {**CTX, "timings": {"max_num_seqs": 24, "ttft_median_ms": 900.0,
                              "itl_median_ms": 30.0, "compiles_in_window": 0,
                              "window_s": 45.0}}
    assert _load("layer_metrics", name.split(".")[0]).compute(ctx) is not None


# -- the readers ---------------------------------------------------------------

STATE = 8 * 8256 * 129 * 4          # the published state, bytes
COUNTERS = {
    "serving/step_time{phase=decode}:count": 100,
    "serving/step_time{phase=decode}:sum": 3.0,
    "serving/step_time{phase=prefill}:count": 10,
    "serving/step_time{phase=prefill}:sum": 1.0,
    "serving/step_wait{phase=decode}:sum": 2.0,
    "serving/step_wait{phase=decode}:count": 100,
    "serving/decode_tokens": 2300,
    "serving/preemptions": 0,
    "serving/host_stalls": 0,
    "serving/host_stall_seconds": 0.0,
    "serving/steps_dispatched{in_flight=1}": 110,
    "serving/steps_dispatched{in_flight=0}": 0,
    # 23 live rows a step
    "serving/state_slot_steps{group=retention}": 100 * 23,
    "serving/retention_tokens{phase=decode}": 100 * 23 * 6,
    **{f"serving/host_{kind}{{phase={ph}}}{tail}": 0.01
       for kind, tail in (("time", ":sum"), ("cpu", ""))
       for ph in ("engine/schedule", "engine/prepare",
                  "engine/sample_dispatch", "engine/emit", "engine/retire",
                  "api/push_progress")},
}
_DECODE = ('%retention_decode.{} = (f32[24,8,136,8]{{3,2,1,0}}, '
           'f32[25,8,65,136,128]{{4,3,2,1,0}}) custom-call(%a, %b), '
           'custom_call_target="tpu_custom_call"')
_PREFILL = ('%retention_prefill.{} = (bf16[1,8,{},1280,128]{{4,3,2,1,0}}, '
            'f32[25,8,65,136,128]{{4,3,2,1,0}}) custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call"')
# two decode steps (12 kernel calls of 3 ms) and one prompt of 4,096
# positions (6 calls of 8 ms, 16 chunks each); 36 ms of other operations
EVENTS = {"devices": {"/device:TPU:0": [
    [_DECODE.format(i), i * 4000000, 3000000] for i in range(12)] + [
    [_PREFILL.format(i, 16), 50000000 + i * 9000000, 8000000]
    for i in range(6)] + [
    ["%fusion.9 = bf16[24,17408]{1,0} fusion(%p)", 110000000, 36000000]]},
    "host": []}
CTX = {"counters": COUNTERS, "config": CONFIG, "traffic": TRAFFIC,
       "events": EVENTS, "timings": {},
       "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
_FLOPS = 2 * 8256 * 129 * 48 + 40 * 2 * 257 * 257 / 2
WANT = {
    # 23 rows x 2 x 34.08 MB over 819 GB/s, over 3 ms a call
    "retention_decode_roofline": 100 * (23 * 2 * STATE / 819e9) / 3e-3,
    # 4,096 positions through the MXU at its peak, over 8 ms a call
    "retention_prefill_roofline": 100 * (4096 * _FLOPS / 197e12) / 8e-3,
    "retention_time_share": 100 * (36 + 48) / 120,
    "state_bytes_per_decode_step": 23 * 6 * 2 * STATE,
}


@pytest.mark.parametrize("stem", sorted(WANT))
def test_reader_on_hand_made_numbers(stem):
    got = _load("layer_metrics", stem).compute(CTX)
    assert got == pytest.approx(WANT[stem])
    if stem != "state_bytes_per_decode_step":
        assert 0 < got <= 100


def test_prefill_roofline_takes_the_states_bytes_for_a_short_call():
    """A call of one chunk of a chip whose MXU is fast: reading and
    writing the state once is the longer side."""
    fast = {**CTX, "peaks": {"hbm_bytes_per_s": 819e9,
                             "bf16_flops_per_s": 197e14},
            "events": {"devices": {"/device:TPU:0": [
                [_PREFILL.format(0, 1), 0, 1000000]]}, "host": []}}
    got = _load("layer_metrics", "retention_prefill_roofline").compute(fast)
    assert got == pytest.approx(100 * (2 * STATE / 819e9) / 1e-3)


@pytest.mark.parametrize("stem", sorted(WANT))
def test_reader_finds_nothing_on_another_program(stem):
    """The recorded slice of a GPT training step, a program without the
    counters, a configuration of another family: None, never a raise (the
    driver runs the readers over the parent too)."""
    reader = _load("layer_metrics", stem)
    if stem != "state_bytes_per_decode_step":
        assert reader.compute({**CTX, "events": SLICE}) is None
    if stem in ("retention_decode_roofline", "state_bytes_per_decode_step"):
        assert reader.compute({**CTX, "counters": {}}) is None
        assert reader.compute({**CTX, "counters": {
            "serving/step_time{phase=decode}:count": 5}}) is None
    if stem != "retention_time_share":
        for other in ("lfm2-24b-a2b-l9", "gpt3-1.3b"):
            with open(os.path.join(BENCH, "configs", other + ".json")) as f:
                assert reader.compute({**CTX,
                                       "config": json.load(f)}) is None


def test_bytes_and_operations_from_the_published_shapes():
    from paddle_tpu.ops import power_retention as pr

    assert retention_ops.retention_layers(CONFIG) == 6
    assert retention_ops.state_bytes(CONFIG) == STATE == 34080768
    assert STATE == pr.published_state_numbers(8, 128) * 4
    assert retention_ops.decode_bytes_per_row_layer(CONFIG) == 2 * STATE
    assert retention_ops.prefill_flops_per_token_layer(CONFIG) == _FLOPS
    # the names and the chunk are the program's
    assert retention_ops.CHUNK == pr.PREFILL_CHUNK
    assert retention_ops.PREFILL_KERNEL == pr.PREFILL_KERNEL
    assert retention_ops.DECODE_KERNEL == pr.DECODE_KERNEL
    assert retention_ops.decode_rows({}) is None
    assert retention_ops.prefill_calls(EVENTS, CONFIG) == [
        (4096, 8e-3)] * 6
    # what the pool keeps is more, never less: a share cannot pass 100%
    assert 4 * 8 * 65 * 136 * 128 >= STATE


# -- kind `serve_family_paths`: two checks decided from data -------------------

def test_kernel_paths_come_from_the_configuration():
    kind = _load("kinds", "serve_family_paths")
    harness = CONFIG["harness"]
    took = {"retention_prefill_kernel": 3, "retention_decode_kernel": 1}
    assert kind.kernel_paths_ok(took, harness)
    assert not kind.kernel_paths_ok({"retention_prefill_kernel": 3}, harness)
    assert not kind.kernel_paths_ok(
        {**took, "retention_fallback:head_geometry": 1}, harness)
    allowed = {"kernel_paths": {**harness["kernel_paths"],
                                "allowed_fallbacks": [
                                    "retention_fallback:head_geometry"]}}
    assert kind.kernel_paths_ok(
        {**took, "retention_fallback:head_geometry": 1}, allowed)
    # without the key: kind `serve_family`'s rule, variants counted
    old = {"attn_kernel:grouped": 3, "ragged_kernel": 1,
           "ragged_kernel:head_products": 1,
           "ragged_fallback:chunk_gt_1": 2}
    assert kind.kernel_paths_ok(old, {})
    assert not kind.kernel_paths_ok({"ragged_kernel": 1}, {})
    assert not kind.kernel_paths_ok(took, {})


@pytest.mark.parametrize("experts", [False, True],
                         ids=["dense", "routed-experts"])
def test_no_pair_dropped_only_where_the_family_routes(experts):
    kind = _load("kinds", "serve_family_paths")
    config = {"harness": CONFIG["harness"]}
    if experts:
        config["num_experts_per_tok"] = 4
    took = {"retention_prefill_kernel": 1, "retention_decode_kernel": 1}
    checks = kind.family_checks(config, took, 0 if experts else None)
    assert checks["kernel_paths"] is True
    assert ("no_pair_dropped" in checks) == experts
    if experts:
        assert checks["no_pair_dropped"] is True
        assert kind.family_checks(config, took, 7)["no_pair_dropped"] is False
        # no token counted: `_pairs_dropped` says None, and that fails
        assert kind.family_checks(config, took,
                                  None)["no_pair_dropped"] is False


@pytest.mark.parametrize("after,least,most", [
    (0.1, 0.2, 0.45), (0.35, 0.3, 0.6), (None, 0.5, 0.8)],
    ids=["prefill-in-the-slice", "prefill-after-it", "no-prefill"])
def test_traced_slice_waits_for_a_prefill_step(after, least, most):
    """0.2 s of slice, up to 0.3 s more for a prefill step's readback."""
    import threading
    import time

    kind = _load("kinds", "serve_family_paths")
    steps = {"count": 3, "sum": 0.5}

    class Monitor:
        @staticmethod
        def snapshot():
            return {"serving/step_time": {"phase=prefill": dict(steps)}}

    if after is not None:
        threading.Timer(after, steps.update, [{"count": 4}]).start()
    t0 = time.perf_counter()
    kind.sleep_through_a_prefill(Monitor, 0.2, extra=0.3)
    assert least <= time.perf_counter() - t0 <= most


def test_runner_frees_the_state_pools_too():
    kind = _load("kinds", "serve_family_paths")

    class Pool:
        def __init__(self, *names):
            self.pool_names = names
            for n in names:
                setattr(self, n, [object()])

    class Engine:
        caches = {"full": Pool("k_blocks", "v_blocks")}
        states = {"retention": Pool("state")}

    kind.free_pools(Engine)
    assert Engine.caches["full"].k_blocks is None
    assert Engine.caches["full"].v_blocks is None
    assert Engine.states["retention"].state is None
