"""What the Falcon-H1 cell adds under `benchmark/`: the configuration
against the catalog row it was cut from (the row's published `config` is
written out below), the arithmetic of its parameters and memories, its
traffic mix, the manifest's new entries, the three new readers on a
cut slice of events, and kind `serve_state_limits`' state probes on a
tiny engine on the CPU.  The manifest is pinned by MEMBERSHIP (`name in
list`), never by position, so the next cell appended turns nothing here
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

from benchmark.kinds.serve_family_paths import family_checks  # noqa: E402
from benchmark.lib import family, ssd_ops  # noqa: E402

NAME = "falcon-h1-34b-l6"
CELL = NAME + ".shortchat-c64"
with open(os.path.join(BENCH, "configs", NAME + ".json")) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(BENCH, "traffic", "shortchat-c64.json")) as _f:
    TRAFFIC = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
SOURCE = ("https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct/blob/main/"
          "config.json")
# the catalog row `Falcon-H1-34B-Instruct`'s `config`, as published
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804, "lm_head_multiplier": 0.0078125,
    "mamba_chunk_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 128, "mamba_d_ssm": 4096, "mamba_d_state": 256,
    "mamba_expand": 2, "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845, "tie_word_embeddings": False,
    "vocab_size": 261120}
NOT_PUBLISHED = ("source", "reduced", "published", "deployment", "assumed",
                 "harness", "initializer_range", "state_dtype",
                 "A_initializer_range", "time_step_min", "time_step_max",
                 "D_initial")
WIDTHS = ("hidden_size", "intermediate_size", "head_dim",
          "num_attention_heads", "num_key_value_heads", "mamba_d_head",
          "mamba_d_ssm", "mamba_d_state", "mamba_n_heads", "mamba_n_groups",
          "mamba_d_conv", "mamba_chunk_size", "vocab_size")
OWN = ("ssd_decode_roofline.serve", "ssd_prefill_roofline.serve",
       "ssd_time_share.serve")


def _load(directory, name):
    path = os.path.join(BENCH, directory, name + ".py")
    spec = importlib.util.spec_from_file_location(f"_bench_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _metric(name):
    metric, = [m for m in MANIFEST["per_layer"] + MANIFEST["end_to_end"]
               if m["name"] == name]
    return metric


# -- the configuration ---------------------------------------------------------

def test_source_is_the_catalogs():
    assert CONFIG["source"].startswith(SOURCE)
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert CONFIG["published"] == {"num_hidden_layers": 72}


@pytest.mark.parametrize("key", sorted(k for k in CONFIG
                                       if k not in NOT_PUBLISHED))
def test_config_key_equals_the_published(key):
    assert key in PUBLISHED
    if key == "num_hidden_layers":
        assert (CONFIG[key], PUBLISHED[key]) == (6, 72)
    else:
        assert CONFIG[key] == PUBLISHED[key]


def test_config_leaves_no_published_key_out_and_cuts_no_width():
    assert set(PUBLISHED) <= set(CONFIG)
    for key in WIDTHS:
        assert CONFIG[key] == PUBLISHED[key], key
    assert CONFIG["state_dtype"] == "float32"
    assert CONFIG["harness"]["dtype"] == "bfloat16"
    assert CONFIG["harness"]["kernel_paths"] == {
        "required": ["ssd_prefill_kernel", "ssd_decode_kernel",
                     "attn_kernel", "ragged_kernel"],
        "allowed_fallbacks": []}
    for said in ("multipliers", "gated norm", "rotary positions",
                 "A_log, dt_bias, D", "state_dtype", "initializer_range"):
        assert said in CONFIG["assumed"], said
    assert "66 layers left out" in CONFIG["deployment"]


def test_family_builds_the_configuration_as_it_is_run():
    cfg = family.model_config(CONFIG)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.conv_dim) \
        == (6, 261120, 5120)
    assert cfg.rope_theta == 1e11 and isinstance(cfg.rope_theta, float)
    assert cfg.ssm_multipliers == tuple(PUBLISHED["ssm_multipliers"])
    assert (cfg.A_initializer_range, cfg.time_step_min, cfg.time_step_max,
            cfg.D_initial) == ((1.0, 16.0), 0.001, 0.1, 1.0)


def test_reference_makes_the_fixed_arrays_from_the_file():
    """A linear over [1, 16], dt = softplus(dt_bias) log-spaced over
    [0.001, 0.1], D 1, a head each: made by the reference from the
    configuration file, equal to the program's own."""
    import numpy as np

    from benchmark.lib import reference_falcon_h1 as ref
    from paddle_tpu.models.falcon_h1 import fixed_ssm_arrays

    a_log, dt_bias, d = (np.asarray(a, np.float64)
                         for a in ref.fixed_arrays(CONFIG))
    np.testing.assert_allclose(np.exp(a_log), np.linspace(1, 16, 32),
                               rtol=1e-6)
    dt = np.log1p(np.exp(dt_bias))
    np.testing.assert_allclose(dt, np.geomspace(1e-3, 1e-1, 32), rtol=1e-5)
    assert (d == 1).all()
    for own, theirs in zip(fixed_ssm_arrays(family.model_config(CONFIG)),
                           (a_log, dt_bias, d)):
        np.testing.assert_allclose(np.asarray(own), theirs, rtol=1e-5,
                                   atol=1e-6)


def test_parameter_count_to_the_digit():
    c = CONFIG
    h, d = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    attention = h * hq + 2 * h * hkv + hq * h
    ssm, heads = c["mamba_d_ssm"], c["mamba_n_heads"]
    gn = c["mamba_n_groups"] * c["mamba_d_state"]
    conv_dim = ssm + 2 * gn
    proj = 2 * ssm + 2 * gn + heads
    assert proj == 9248 and conv_dim == 5120
    mixer = (h * proj + conv_dim * c["mamba_d_conv"] + conv_dim + 3 * heads
             + ssm + ssm * h)
    mlp = 3 * h * c["intermediate_size"]
    assert (attention, mixer, mlp) == (31457280, 68351072, 330301440)
    layer = attention + mixer + mlp + 2 * h
    assert layer == 430120032
    total = c["num_hidden_layers"] * layer + 2 * c["vocab_size"] * h + h
    assert total == 5254594112
    assert "430,120,032" in c["deployment"]
    assert "5,254,594,112" in c["deployment"]


def test_parameter_count_is_the_models():
    """The model's own parameters, and its fixed arrays, add up to the
    same, built over shapes alone."""
    import numpy as np

    from paddle_tpu.framework.compat import LazyGuard

    with LazyGuard():
        model = family.resolve(CONFIG["harness"]["model"])(
            family.model_config({**CONFIG, "num_hidden_layers": 1,
                                 "vocab_size": 8}))
    drawn = sum(int(np.prod(p.shape)) for p in model.parameters())
    fixed = sum(int(a[0].size) for n, a in model.param_arrays().items()
                if n in ("A_log", "dt_bias", "D"))
    assert drawn + fixed == 430120032 + 2 * 8 * 5120 + 5120


def test_memories_a_sequence_holds():
    """4,194,304 B of SSM state a layer, 30,720 B of convolution state,
    12,288 B of K/V a token over the six layers."""
    assert ssd_ops.state_bytes(CONFIG) == 4194304
    assert ssd_ops.decode_bytes_per_row_layer(CONFIG) == 2 * 4194304
    conv = (CONFIG["mamba_d_conv"] - 1) * 5120 * 2
    assert conv == 30720
    kv = (2 * CONFIG["num_key_value_heads"] * CONFIG["head_dim"] * 2
          * CONFIG["num_hidden_layers"])
    assert kv == 12288
    assert ssd_ops.prefill_flops_per_token_layer(CONFIG) == 5373952
    assert ssd_ops.state_bytes({"hidden_size": 1}) is None


def test_weight_rule_suits_every_parameter_name():
    """Norm scales 1, the convolution's bias 0, every matrix from the
    seed; A_log, dt_bias and D are no parameter at all."""
    from paddle_tpu.models.falcon_h1 import _LAYER

    drawn = [n for n in _LAYER if n not in ("A_log", "dt_bias", "D")]
    rules = {n: family.weight_rule(n) for n in drawn}
    assert [n for n, r in rules.items() if r == "ones"] == [
        "in_norm", "pre_ff_norm", "gate_norm"]
    assert [n for n, r in rules.items() if r == "zeros"] == ["conv_bias"]


# -- the traffic and the manifest ----------------------------------------------

def test_cell_traffic_is_the_stated_mix():
    assert TRAFFIC["kind"] == "serve_state_limits"
    assert TRAFFIC["clients"] == 64
    assert TRAFFIC["prompt_len"] == [[256, 4], [1024, 4], [2048, 2]]
    assert TRAFFIC["max_tokens"] == [[128, 2], [256, 2], [512, 1]]
    assert TRAFFIC["engine"] == {"block_size": 64, "max_num_seqs": 64,
                                 "max_model_len": 2560, "num_blocks": 2560}
    assert (TRAFFIC["warmup_s"], TRAFFIC["trace_s"]) == (8, 2)
    assert TRAFFIC["check_requests"] == len(TRAFFIC["prompt_len"]) == 3
    mean = (sum(v * c for v, c in TRAFFIC["prompt_len"])
            / sum(c for _, c in TRAFFIC["prompt_len"]))
    out = (sum(v * c for v, c in TRAFFIC["max_tokens"])
           / sum(c for _, c in TRAFFIC["max_tokens"]))
    assert (mean, out) == (921.6, 256)
    assert max(v for v, _ in TRAFFIC["prompt_len"]) + max(
        v for v, _ in TRAFFIC["max_tokens"]) == \
        TRAFFIC["engine"]["max_model_len"]
    assert set(TRAFFIC["reference_limits"]) == {
        "logit_margin", "near", "min_share_within", "state_rel_err"}
    assert set(TRAFFIC["reference_limits"]["state_rel_err"]) \
        == {"conv", "ssm"}
    # the served SSM slot is [heads, d_state, d_head]; the reference's
    # [heads, d_head, d_state]
    assert TRAFFIC["state_axes"] == {"ssm": [0, 2, 1]}


def test_manifest_holds_the_configuration_and_the_cell():
    entry, = [c for c in MANIFEST["configs"] if c["name"] == NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["source"] == SOURCE
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["why"] == (
        "Falcon-H1 34B widths: attention and a Mamba-2 mixer in every "
        "layer, 4 MB SSM state a row a layer beside K/V; 6 of 72 layers")
    cell, = [w for w in MANIFEST["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "shortchat-c64", 1)
    assert len(cell["why"]) <= 200
    assert CELL in _metric("serve_tokens_per_s")["workloads"]


@pytest.mark.parametrize("name", OWN)
def test_new_metric_lists_this_cell_alone(name):
    m = _metric(name)
    assert m["workloads"] == [CELL]
    assert (m["moves"], m["layer"], m["unit"], m["source"]) \
        == ("serve_tokens_per_s", "kernels", "%", "device_trace")
    assert os.path.exists(os.path.join(
        BENCH, "layer_metrics", name.split(".")[0] + ".py"))
    assert m["better"] == ("lower" if "time_share" in name else "higher")


# the generic serve lists keye's cell is in, and those it stays off: no
# experts, no window group, another family's kernels, the host phases a
# test pins to the GPT cells
JOINS = ("device_idle_share.serve", "pallas_time_share.serve",
         "decode_step_ms.serve", "prefill_step_ms.serve",
         "prefill_step_share.serve", "ttft_median_ms.serve",
         "itl_median_ms.serve", "batch_occupancy.serve", "preemptions.serve",
         "ttft_p95_ms.unbounded", "itl_p95_ms.unbounded",
         "compiles_in_window.serve", "step_host_ms.serve",
         "step_wait_share.serve", "host_cpu_share.serve",
         "pipeline_full_share.serve", "host_stall_s.serve")
STAYS_OUT = ("moe_time_share.serve", "expert_tokens_per_step.serve",
             "host_gap_share.serve", "schedule_ms.serve",
             "kv_window_held_share.serve", "retention_decode_roofline.serve",
             "state_bytes_per_decode_step.serve", "dsa_time_share.serve")


@pytest.mark.parametrize("name", JOINS)
def test_cell_joins_the_generic_serve_lists(name):
    assert CELL in _metric(name)["workloads"]


@pytest.mark.parametrize("name", STAYS_OUT)
def test_cell_stays_off_the_lists_it_cannot_report(name):
    assert CELL not in _metric(name)["workloads"]


def test_kernel_paths_decide_from_the_configuration():
    took = {"ssd_prefill_kernel": 6, "ssd_decode_kernel": 6,
            "attn_kernel:grouped": 6, "ragged_kernel": 6,
            "ragged_kernel:head_products": 6}
    assert family_checks(CONFIG, took, None) == {"kernel_paths": True}
    assert not family_checks(CONFIG, {**took, "ssd_fallback:geometry": 1},
                             None)["kernel_paths"]
    gone = {k: v for k, v in took.items() if k != "ssd_decode_kernel"}
    assert not family_checks(CONFIG, gone, None)["kernel_paths"]


# -- the readers, on a cut slice of events --------------------------------------

def _event(kernel, results, operands, ns):
    return [f"%{kernel}.3 = ({', '.join(r + '{1,0}' for r in results)}) "
            "custom-call("
            + ", ".join(f"{o}{{1,0}} %a{i}" for i, o in enumerate(operands))
            + '), custom_call_target="tpu_custom_call"', 0, ns]


POOL = "f32[65,32,256,128]"
DECODE = [_event("ssd_decode", ["f32[64,4,8,128]", POOL],
                 ["s32[64]", "f32[64,4,16,128]", "f32[64,2,8,256]",
                  "f32[64,2,256,8]", POOL], 800_000)]
# a prompt of 2,048 positions: 16 chunks of 128 in each of 32 heads
PREFILL = [_event("ssd_prefill", ["f32[1,32,16,128,128]", POOL],
                  ["s32[1]", "f32[1,32,16,128,128]", "f32[1,32,16,128,128]",
                   "f32[1,32,16,8,128]", "bf16[1,2,16,256,128]",
                   "bf16[1,2,16,128,256]", POOL], 400_000)]
OTHER = [["%fusion.1 = bf16[64,5120]{1,0} fusion(bf16[64,5120]{1,0} %x)", 0,
          2_000_000]]
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# cut from a traced run of the cell on a TPU v5e: the six prefill calls of
# one 1,024-token prompt (one a layer), twelve decode calls (two steps),
# twelve of the ragged kernel's and two of the head's beside them; the
# operations' HLO text shortened past their results
with open(os.path.join(os.path.dirname(__file__), "fixtures",
                       "falcon_h1_slice.json")) as _f:
    SLICE = json.load(_f)


def _ctx(events, counters=None, busy=None):
    return {"config": CONFIG, "traffic": TRAFFIC, "peaks": PEAKS,
            "events": {"devices": {"/device:TPU:0": events}},
            "counters": counters or {}, "trace": {"busy_s": busy}}


def test_prefill_calls_read_rows_and_positions():
    assert ssd_ops.prefill_calls({"devices": {"d": PREFILL + DECODE}}) == [
        (1, 2048, 400e-6)]
    assert ssd_ops.is_ssd_kernel(DECODE[0][0])
    assert not ssd_ops.is_ssd_kernel(OTHER[0][0])


def test_decode_roofline_by_hand():
    # 100 decode steps of 48 live rows; the slice: one layer's call, 0.8 ms
    counters = {"serving/step_time{phase=decode}:count": 100,
                "serving/state_slot_steps{group=ssm}": 100 * 48}
    reader = _load("layer_metrics", "ssd_decode_roofline")
    got = reader.compute(_ctx(DECODE + OTHER, counters))
    assert got == pytest.approx(100 * 48 * 2 * 4194304 / 819e9 / 0.8e-3)
    assert 0 < got < 100
    assert reader.compute(_ctx(DECODE, {})) is None
    assert reader.compute(_ctx(OTHER, counters)) is None


def test_prefill_roofline_by_hand():
    reader = _load("layer_metrics", "ssd_prefill_roofline")
    got = reader.compute(_ctx(PREFILL + DECODE))
    assert got == pytest.approx(100 * 2048 * 5373952 / 197e12 / 0.4e-3)
    assert 0 < got < 100
    assert reader.compute(_ctx(DECODE + OTHER)) is None


def test_time_share_by_hand():
    reader = _load("layer_metrics", "ssd_time_share")
    got = reader.compute(_ctx(PREFILL + DECODE + OTHER, busy=3.2e-3))
    assert got == pytest.approx(100 * 1.2 / 3.2)
    assert reader.compute(_ctx(OTHER, busy=2.0e-3)) is None


@pytest.mark.parametrize("stem", [n.split(".")[0] for n in OWN])
def test_readers_find_nothing_in_the_parents_slice(stem):
    """The parent's program has none of these kernels or counters: None,
    never a raise."""
    reader = _load("layer_metrics", stem)
    ctx = _ctx(OTHER, {"serving/step_time{phase=decode}:count": 10},
               busy=2.0e-3)
    assert reader.compute(ctx) is None


@pytest.mark.parametrize("stem", ["ssd_decode_roofline",
                                  "ssd_prefill_roofline"])
def test_rooflines_find_nothing_in_another_configuration(stem):
    reader = _load("layer_metrics", stem)
    other = {k: v for k, v in CONFIG.items() if not k.startswith("mamba_")}
    counters = {"serving/step_time{phase=decode}:count": 100,
                "serving/state_slot_steps{group=ssm}": 100 * 48}
    ctx = {**_ctx(PREFILL + DECODE, counters, busy=2.0e-3),
           "config": other}
    assert reader.compute(ctx) is None


def test_readers_on_a_slice_cut_from_the_chip():
    from benchmark.lib.trace import summarize

    calls = ssd_ops.prefill_calls(SLICE)
    assert [(rows, n) for rows, n, _ in calls] == [(1, 1024)] * 6
    took = sum(t for _, _, t in calls)
    ctx = {"config": CONFIG, "peaks": PEAKS, "events": SLICE,
           "counters": {"serving/step_time{phase=decode}:count": 100,
                        "serving/state_slot_steps{group=ssm}": 100 * 64},
           "trace": summarize(SLICE)}
    got = _load("layer_metrics", "ssd_prefill_roofline").compute(ctx)
    assert got == pytest.approx(100 * 6 * 1024 * 5373952 / 197e12 / took)
    assert 5 < got < 30
    seconds, n = ssd_ops.kernel_call_seconds(SLICE, ssd_ops.DECODE_KERNEL)
    assert n == 12
    got = _load("layer_metrics", "ssd_decode_roofline").compute(ctx)
    assert got == pytest.approx(100 * 64 * 2 * 4194304 / 819e9
                                / (seconds / 12))
    assert 40 < got < 100
    ssd = sum(d for name, _, d in SLICE["devices"]["/device:TPU:0"]
              if ssd_ops.is_ssd_kernel(name)) / 1e9
    got = _load("layer_metrics", "ssd_time_share").compute(ctx)
    assert got == pytest.approx(100 * ssd / ctx["trace"]["busy_s"])
    assert 0 < got < 100


# -- kind `serve_state_limits`: the state probes, on the CPU -------------------

# the configuration at a tiny size in float32: the engine's states then
# differ from the reference's by the order of a sum alone
TINY = {"vocab_size": 96, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 128, "mamba_n_heads": 4, "mamba_d_head": 16,
        "mamba_d_ssm": 64, "mamba_d_state": 16, "mamba_n_groups": 2,
        "mamba_chunk_size": 16, "max_position_embeddings": 512}
PROBE_TRAFFIC = {"prompt_len": [[17, 3], [40, 1]], "max_tokens": [[12, 1]],
                 "state_axes": TRAFFIC["state_axes"],
                 "reference_limits": {"state_rel_err": {"conv": 1e-4,
                                                        "ssm": 1e-4}}}


@pytest.fixture(scope="module")
def tiny_probes():
    from paddle_tpu.serving import EngineConfig, LLMEngine

    kind = _load("kinds", "serve_state_limits")
    config = {**CONFIG, **TINY,
              "harness": {**CONFIG["harness"], "dtype": "float32"}}
    model, cfg = family.build_model(config, 2 ** 31 + 11)
    model.eval()
    engine = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4,
                                           max_model_len=128))
    probes = kind.probe(engine, PROBE_TRAFFIC, cfg.vocab_size, 5)
    return kind, model, config, engine, probes


def test_probes_read_each_memory_while_the_request_lives(tiny_probes):
    """One probe a prompt length, longest first, read after the prompt and
    all but the last two or three of its 12 tokens, every layer of both
    state groups, and released afterwards."""
    _, _, _, engine, probes = tiny_probes
    assert [len(ids) - 1 == n for ids, n, _ in probes] == [True, True]
    assert [len(ids) for ids, _, _ in probes][0] - 40 in (10, 11)
    assert [len(ids) for ids, _, _ in probes][1] - 17 in (10, 11)
    for _, _, held in probes:
        assert set(held) == {"conv", "ssm"}
        assert [a.shape for a in held["ssm"]] == [(4, 16, 16)] * 2
        assert [a.shape for a in held["conv"]] == [(3, 128)] * 2
    assert not engine._requests
    assert all(s.slots_in_use == 0 for s in engine.states.values())


def test_served_states_are_the_references(tiny_probes):
    kind, model, config, _, probes = tiny_probes
    read = kind.state_readings(model, config, probes, PROBE_TRAFFIC)
    assert set(read) == {"conv", "ssm"}
    assert read["ssm"] < 1e-4 and read["conv"] < 1e-5, read
    assert kind.state_verdict(read, PROBE_TRAFFIC["reference_limits"])


@pytest.mark.parametrize("fault", ["bf16_state", "no_ssm_carry", "fp8"])
def test_a_faulty_states_reading_fails_the_limit(tiny_probes, fault):
    """Against the reference computed with a fault the SSM state reads
    ten times the limit or more: a state rounded to bfloat16, one not
    carried across chunks, products in float8."""
    kind, model, config, _, probes = tiny_probes
    read = kind.state_readings(model, config, probes, PROBE_TRAFFIC, fault)
    assert read["ssm"] > 1e-3, read
    assert not kind.state_verdict(read, PROBE_TRAFFIC["reference_limits"])


def test_the_reading_sees_every_decode_position(tiny_probes):
    """The same served states against the reference's after one position
    fewer read far off: a decode step that did not write its slot back
    would read so."""
    kind, model, config, _, probes = tiny_probes
    short = [(ids, n - 1, held) for ids, n, held in probes]
    read = kind.state_readings(model, config, short, PROBE_TRAFFIC)
    assert read["ssm"] > 1e-2 and read["conv"] > 1e-2, read


def test_state_verdict_wants_every_group_within_its_limit():
    kind = _load("kinds", "serve_state_limits")
    limits = {"state_rel_err": {"conv": 0.1, "ssm": 0.2}}
    assert kind.state_verdict({"conv": 0.1, "ssm": 0.2}, limits)
    assert not kind.state_verdict({"conv": 0.1, "ssm": 0.21}, limits)
    assert not kind.state_verdict({"ssm": 0.0}, limits)
    assert not kind.state_verdict(None, limits)


def test_run_probes_before_the_pools_go_and_adds_the_state_check(
        tiny_probes, monkeypatch):
    """`run` hands kind `serve_family_limits`' run a `free_pools` that
    probes the engine first and a `compare` that reads the states, and
    decides `reference_state` from that reading (kind
    `serve_family_limits`' run stood in for by what it calls)."""
    from benchmark.kinds import serve_family_limits as limits_kind
    from benchmark.kinds import serve_family_paths as paths

    kind, model, config, engine, _ = tiny_probes
    kept = (paths.free_pools, limits_kind.compare)

    def stand_in(spec):
        paths.free_pools(engine)
        limits_kind.compare(model, config, [], spec["traffic"], spec["seed"])
        return {"checks": {"requests_ok": True}}

    monkeypatch.setattr(limits_kind, "run", stand_in)
    monkeypatch.delenv("BENCH_REFERENCE_FAULT", raising=False)
    spec = {"traffic": {**PROBE_TRAFFIC, "check_requests": 3},
            "config": config, "seed": 5}
    out = kind.run(spec)
    assert out["checks"] == {"requests_ok": True, "reference_state": True}
    assert all(st.state is None for st in engine.states.values())
    assert (paths.free_pools, limits_kind.compare) == kept
