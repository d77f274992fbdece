"""mistral4 through `LLMEngine` at a small size on the CPU (hidden 64, 4
heads of 8 + 8 over a latent row of 24 + 8, block 4, 3 layers, 8 experts
top-2 + a shared one, the original context shrunk to 32 positions), seeded
weights, against the plain reference `benchmark/lib/reference_mistral4.py`;
the latent group's ONE pool a layer through the allocator's every path;
the expert layer's eight shares; YaRN, the interleave and the query scale;
the latent decode kernel in interpret mode; and what the engine refuses.
Nothing here is a measurement.
"""
import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu  # noqa: E402,F401
from benchmark.lib import reference_mistral4 as ref  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.models import (LatentSpec, Mistral4ForCausalLM,  # noqa: E402
                               mistral4_test_config)
from paddle_tpu.models.mistral4 import (query_scale,  # noqa: E402
                                        rope_interleaved, yarn_inv_freq)
from paddle_tpu.ops import pallas_ops as po  # noqa: E402
from paddle_tpu.ops import ragged_paged_attention as rp  # noqa: E402
from paddle_tpu.ops.paged_attention import (  # noqa: E402
    latent_cache_update_arrays, latent_paged_attention_arrays,
    latent_pool_lanes)
from paddle_tpu.parallel.moe import held_experts_arrays  # noqa: E402
from paddle_tpu.serving import EngineConfig, LLMEngine  # noqa: E402
from paddle_tpu.serving.kv_cache import BlockKVCache  # noqa: E402
from paddle_tpu.serving.scheduler import SamplingParams  # noqa: E402

BS = 4
# float32 on both sides: the orders of the sums differ, and the absorbed
# form sums over the latent where the reference sums over a head (2e-5
# logits at most, measured 4e-6); a wrong block, mask, frequency, scale or
# expert moves a logit by hundredths, and bfloat16 in place of float32 by
# 1e-2 (the last test of section (a))
LOGIT_TOL = 1e-4
NEAR_TIE = 1e-3


def _seeded(cfg, seed=0):
    """A model of `cfg` with weights from `seed`: matrices N(0, 0.08),
    norm scales near 1 (not exactly: a forgotten norm must show)."""
    model = Mistral4ForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for n, p in model.named_parameters():
        if "norm" in n:
            val = 1 + 0.1 * rng.standard_normal(p.shape)
        elif "expert_bias" in n:
            continue
        else:
            val = 0.08 * rng.standard_normal(p.shape)
        p._data = jnp.asarray(val, p._data.dtype)
    model.eval()
    return model


def _cfg_dict(cfg):
    d = dataclasses.asdict(cfg)
    d["harness"] = {"kwargs": {"first_expert": cfg.first_expert,
                               "router_experts": cfg.router_experts}}
    return d


@pytest.fixture(scope="module")
def tiny():
    cfg = mistral4_test_config()
    return _seeded(cfg), cfg


def _ref_logits(model, cfg, seq, **kw):
    return np.asarray(ref.logits(ref.params_from_model(model),
                                 jnp.asarray(seq), _cfg_dict(cfg), **kw))


def _margins(model, cfg, seq, prompt_len, **kw):
    """How far under each position's largest reference logit the served
    tokens' logits lie."""
    ids = jnp.asarray(seq)
    margins, _ = ref._margins(
        ref.logits(ref.params_from_model(model), ids, _cfg_dict(cfg), **kw),
        ids)
    return np.asarray(margins)[prompt_len - 1:]


def _engine(model, **kw):
    base = dict(block_size=BS, max_num_seqs=4, max_model_len=96)
    base.update(kw)
    return LLMEngine(model, EngineConfig(**base))


def _prompts(cfg, lens, seed=7):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab_size, n)) for n in lens]


def _served_logits(eng, prompts, sp):
    """Every logits row the engine sampled from, by request: a list of
    [V] arrays a request, the prefill's first."""
    seen = {}
    sample = eng._sample_rows

    def spy(rows, logits, stats=None):
        lg = np.asarray(logits, np.float32)
        for i, r in enumerate(rows):
            seen.setdefault(r.req_id, []).append(lg[i])
        return sample(rows, logits, stats)

    eng._sample_rows = spy
    outs = eng.generate(prompts, sp)
    return outs, [seen[i] for i in sorted(seen)]


# -- (a) the model and the engine against the reference -----------------------

def test_forward_matches_reference_logits(tiny):
    """Whole sequences past the shrunk original context, no cache, the
    expanded form on both sides: every logit."""
    model, cfg = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 75))
    got = np.asarray(model(jnp.asarray(ids))._data)
    for row, g in zip(ids, got):
        want = _ref_logits(model, cfg, row)
        assert want.std() > 0.3
        np.testing.assert_allclose(g, want, atol=LOGIT_TOL, rtol=0)


def test_absorbed_form_equals_the_expanded_one(tiny):
    """`W_uk` folded into the query and `W_uv` out of the result, every
    head against the one latent row (here the chunk's own rows, densely),
    is the per-head attention over expanded keys and values."""
    model, cfg = tiny
    ids = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (2, 70)))
    form, params = model.serving_form(), model.param_arrays()
    seen = jnp.tril(jnp.ones((70, 70), bool))

    def absorbed(spec):
        def latent_fn(rows, whole, stored):
            def attend(q):
                logits = jnp.einsum("bqhd,bkd->bhqk", q, rows) * spec.scale
                probs = jax.nn.softmax(jnp.where(seen, logits, -1e30), -1)
                return jnp.einsum("bhqk,bkd->bqhd", probs,
                                  rows[..., :spec.value_dim])
            return stored(attend), None
        return latent_fn

    def forward(params, ids):
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        h = form.embed(params, ids, pos)
        for l, spec in enumerate(form.layer_specs):
            h, _, _ = form.layer(l, params, h, pos, absorbed(spec))
        return form.logits(params, h)

    np.testing.assert_allclose(
        np.asarray(jax.jit(forward)(params, ids)),
        np.asarray(jax.jit(model.forward_arrays)(params, ids)),
        atol=2e-5, rtol=0)


@pytest.mark.parametrize("lens,rows", [
    ((5, 13, 41), 4), ((3, 8, 9, 33), 4), ((1, 2), 4), ((70,), 2)],
    ids=["mixed", "block-edges", "shortest", "past-the-original-context"])
def test_engine_logits_are_the_references(tiny, lens, rows):
    """Prefill (expanded, flash) then decode through the latent pool
    (absorbed), a mixed batch, fewer rows than the program's: every
    logits row the engine sampled from is the reference's row of its full
    forward over prompt + served tokens, within LOGIT_TOL."""
    model, cfg = tiny
    prompts = _prompts(cfg, lens)
    eng = _engine(model, max_num_seqs=rows)
    outs, logits = _served_logits(eng, prompts,
                                  SamplingParams(max_new_tokens=12))
    for p, o, rows_ in zip(prompts, outs, logits):
        assert len(o) == len(p) + 12 and len(rows_) == 12
        want = _ref_logits(model, cfg, o)[len(p) - 1:-1]
        np.testing.assert_allclose(np.stack(rows_), want, atol=LOGIT_TOL,
                                   rtol=0)
        assert _margins(model, cfg, o, len(p)).max() <= NEAR_TIE
    assert eng.cache.blocks_in_use == 0


def test_bfloat16_in_place_of_float32_fails_the_tolerance(tiny):
    """The same engine over the same weights rounded to bfloat16 misses
    LOGIT_TOL by two orders: the tolerance tells the precisions apart."""
    model, cfg = tiny
    low = _seeded(cfg)
    low.to(dtype="bfloat16")
    prompt, = _prompts(cfg, (21,))
    outs, logits = _served_logits(_engine(low), [prompt],
                                  SamplingParams(max_new_tokens=6))
    want = _ref_logits(model, cfg, outs[0])[len(prompt) - 1:-1]
    assert np.abs(np.stack(logits[0]) - want).max() > 30 * LOGIT_TOL


@pytest.mark.parametrize("budget", [8, 5], ids=lambda b: f"chunks-of-{b}")
def test_chunked_prefill_attends_the_stored_rows(tiny, budget):
    """A prompt fed in chunks under `max_num_batched_tokens`: every
    continuation chunk is the absorbed form at C > 1 over the pool (the
    fallback), and the tokens are the reference's."""
    model, cfg = tiny
    prompt, = _prompts(cfg, (43,), seed=5)
    eng = _engine(model, max_num_seqs=2, max_num_batched_tokens=budget)
    out, = eng.generate([prompt], SamplingParams(max_new_tokens=6))
    assert _margins(model, cfg, out, len(prompt)).max() <= NEAR_TIE


# -- (b) the latent group: one pool a layer, every allocator path -------------

def test_a_latent_group_keeps_one_pool_a_layer(tiny):
    model, cfg = tiny
    eng = _engine(model)
    assert list(eng.caches) == ["latent"] and not eng.states
    cache = eng.cache
    assert cache.pool_names == ("k_blocks",) and cache.v_blocks is None
    assert len(eng._kv_flat()) == cfg.num_hidden_layers
    lanes = latent_pool_lanes(cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    assert lanes == 128 and latent_pool_lanes(320) == 384
    assert cache.k_blocks[0].shape == (cache.num_blocks, BS, lanes)
    # one pool: half of what a K pool and a V pool of that row would cost
    assert cache.bytes_per_block == cfg.num_hidden_layers * BS * lanes * 4
    spec = eng.form.layer_specs[0]
    assert isinstance(spec, LatentSpec) and spec.pool_row() == (1, lanes, 1)
    assert (spec.key_dim, spec.value_dim) == (32, 24)
    assert spec.scale == pytest.approx(
        16 ** -0.5 * (0.1 * math.log(8.0) + 1) ** 2)
    with pytest.raises(ValueError, match="latent group"):
        BlockKVCache(1, 4, 4, 1, 128, value_in_key=True, kv_quant="int8")


def test_preemption_swaps_the_latent_rows_and_the_tokens_do_not_move(tiny):
    """A pool too small for three long rows: the scheduler evicts, swaps
    back in, and the tokens are those of an engine that never preempted."""
    model, cfg = tiny
    prompts = _prompts(cfg, (14, 15, 13), seed=3)
    sp = SamplingParams(max_new_tokens=14)
    want = _engine(model).generate(prompts, sp)
    tight = _engine(model, num_blocks=16)
    got = tight.generate(prompts, sp)
    assert tight.scheduler.num_evictions > 0 < tight.scheduler.num_swap_ins
    for w, g, p in zip(want, got, prompts):
        np.testing.assert_array_equal(w, g)
        assert _margins(model, cfg, g, len(p)).max() <= NEAR_TIE


def test_swap_out_and_in_restore_the_rows_bit_for_bit(tiny):
    model, cfg = tiny
    eng = _engine(model)
    rid = eng.add_request(_prompts(cfg, (11,))[0],
                          SamplingParams(max_new_tokens=8))
    for _ in range(3):
        eng.step()
    cache = eng.cache
    table = np.asarray(cache.block_table(rid))
    was = [np.asarray(p[table]) for p in cache.k_blocks]
    saved = cache.swap_out(rid)
    assert set(saved) == {"len", "k"} and cache.blocks_in_use == 0
    cache.allocate("other", 3 * BS)           # other blocks on the way back
    cache.swap_in(rid, saved)
    now = np.asarray(cache.block_table(rid))
    assert list(now) != list(table)
    for w, p in zip(was, cache.k_blocks):
        assert np.abs(w).max() > 0
        np.testing.assert_array_equal(w, np.asarray(p[now]))


def test_export_and_adopt_carry_the_latent_rows_bit_exactly(tiny):
    model, cfg = tiny
    prompt, other = _prompts(cfg, (11, 6), seed=9)
    sp = SamplingParams(max_new_tokens=10)
    want, = _engine(model).generate([prompt], sp)
    src, dst = _engine(model), _engine(model)
    rid = src.add_request(prompt, sp)
    src.add_request(other, sp)            # so the blocks differ
    for _ in range(5):
        src.step()
    table = np.asarray(src.cache.block_table(rid))
    was = [np.asarray(p[table]) for p in src.cache.k_blocks]
    handoff = src.export_request(rid)
    assert rid not in src.cache._tables
    assert "v" not in handoff["kv"]
    for w, shipped in zip(was, handoff["kv"]["k"]):
        np.testing.assert_array_equal(w, shipped)
    dst.add_request(other, sp)            # another block order over there
    dst.step()
    new = dst.adopt_request(handoff["prompt_ids"], handoff["params"],
                            handoff["output_ids"], handoff["key"],
                            handoff["kv"])
    while dst.has_unfinished():
        dst.step()
    np.testing.assert_array_equal(dst.request_output(new), want)


def test_fork_shares_the_rows_and_copies_the_last_block(tiny):
    """A forked child shares its parent's latent blocks but the last,
    copied bit for bit, and decodes the parent's own continuation."""
    model, cfg = tiny
    prompt, = _prompts(cfg, (9,), seed=11)
    eng = _engine(model)
    rid = eng.add_request(prompt, SamplingParams(max_new_tokens=8))
    for _ in range(3):
        eng.step()
    kid = eng.fork_request(rid, SamplingParams(max_new_tokens=5))
    mine, theirs = eng.cache.block_table(kid), eng.cache.block_table(rid)
    assert mine[:-1] == theirs[:-1] and mine[-1] != theirs[-1]
    for p in eng.cache.k_blocks:
        np.testing.assert_array_equal(np.asarray(p[mine[-1]]),
                                      np.asarray(p[theirs[-1]]))
    while eng.has_unfinished():
        eng.step()
    parent, child = eng.request_output(rid), eng.request_output(kid)
    np.testing.assert_array_equal(child[:len(parent)][len(prompt) + 3:],
                                  parent[len(prompt) + 3:len(child)])
    assert _margins(model, cfg, child, len(prompt) + 3).max() <= NEAR_TIE


# -- (c) what the engine refuses, and what it counts ---------------------------

@pytest.mark.parametrize("option", [
    {"kv_cache_dtype": "int8"}, {"speculative_tokens": 2},
    {"enable_prefix_caching": True}], ids=lambda o: next(iter(o)))
def test_options_not_carried_to_the_family_raise_by_name(tiny, option):
    model, _ = tiny
    with pytest.raises(ValueError, match=next(iter(option))):
        LLMEngine(model, EngineConfig(block_size=BS, max_model_len=32,
                                      **option))


@pytest.mark.parametrize("key,value", [
    ("first_k_dense_replace", 1), ("n_group", 2), ("rope_interleave", False),
    ("norm_topk_prob", False), ("tie_word_embeddings", True)])
def test_config_refuses_what_the_family_file_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        mistral4_test_config(**{key: value})


def test_latent_group_counters_and_scopes(tiny):
    model, cfg = tiny
    eng = _engine(model)

    def val(name, **labels):
        return monitor.snapshot().get(name, {}).get(
            ",".join(f"{k}={v}" for k, v in sorted(labels.items())), 0)

    live0 = val("serving/kv_tokens_live", group="latent")
    held0 = val("serving/kv_block_steps", group="latent")
    pairs0 = val("serving/moe_pairs", phase="decode", where="held")
    rid = eng.add_request(_prompts(cfg, (7,))[0],
                          SamplingParams(max_new_tokens=4))
    eng.step()
    assert val("serving/kv_blocks_in_use", group="latent") == 2
    while eng.has_unfinished():
        eng.step()
    eng.release_request(rid)
    # 3 decode steps of one row at lengths 8, 9, 10, in 2, 3, 3 blocks of 4
    assert val("serving/kv_tokens_live", group="latent") - live0 == 27
    assert val("serving/kv_block_steps", group="latent") - held0 == 8
    assert val("serving/moe_pairs", phase="decode", where="held") \
        - pairs0 == 3 * 3 * 2
    assert val("serving/kv_blocks_in_use", group="latent") == 0
    # the scopes the trace's readers look for, in the programs that hold them
    toks, pos0, lens, tables, slots, srows = eng._decode_inputs([], [], 4, 1)
    decode = eng._get_ragged_exec(4, 1).lower(
        eng._param_arrays(), eng._kv_flat(), toks, pos0, lens, tables,
        slots, srows).as_text(debug_info=True)
    for scope in ("attn/latent", "mla/absorb", "mistral4/router",
                  "mistral4/experts", "mistral4/shared_expert"):
        assert scope in decode, scope
    assert "mla/expand" not in decode
    prefill = eng._get_prefill_exec(8).lower(
        eng._param_arrays(), eng._kv_flat(), np.zeros((1, 8), np.int32),
        (np.arange(8, dtype=np.int32)[None],), ()).as_text(debug_info=True)
    assert "mla/expand" in prefill and "attn/latent" in prefill
    assert "mla/absorb" not in prefill


# -- (d) the expert layer's shares ---------------------------------------------

def test_eight_shares_add_up_to_the_uncut_layer(tiny):
    """16 experts over 8 chips, 2 a chip, each share routing over all 16:
    the eight partial results, the shared expert counted ONCE, add up to
    what the uncut reference gives for the whole layer; and the reference
    cut the same way adds up to it too."""
    cfg = mistral4_test_config(n_routed_experts=16, num_hidden_layers=1)
    model = _seeded(cfg, seed=4)
    params = ref.params_from_model(model)
    e = {n: params[n][0] for n in ref._MOE}
    m = jnp.asarray(np.random.default_rng(3).standard_normal((24, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref._expert_mlp(m, e, range(16), 0, 2, 1.0, None))
        shared = np.asarray(ref._shared(
            m, e["shared_gate_w"], e["shared_up_w"], e["shared_down_w"],
            fault=None))
        ref_parts = sum(
            np.asarray(ref._expert_mlp(
                m, {**e, **{k: e[k][first:first + 2] for k in (
                    "exp_gate_w", "exp_up_w", "exp_down_w")}},
                range(first, first + 2), first, 2, 1.0, None, shared=False))
            for first in range(0, 16, 2))
    np.testing.assert_allclose(ref_parts + shared, uncut, atol=1e-5)
    parts, held = 0.0, 0
    for first in range(0, 16, 2):
        y, stats = held_experts_arrays(
            m, e["router_w"], e["expert_bias"],
            tuple(e[k][first:first + 2] for k in (
                "exp_gate_w", "exp_up_w", "exp_down_w")),
            first, 2, 2, 1.0)
        parts = parts + np.asarray(y)
        held += int(stats[0])
        assert int(stats[0]) + int(stats[1]) == 2 * 24
    assert held == 2 * 24                  # every pair on exactly one chip
    np.testing.assert_allclose(parts + shared, uncut, atol=1e-5, rtol=1e-5)


# -- (e) positions ---------------------------------------------------------------

def test_yarn_frequencies_interleave_and_query_scale():
    rp_ = mistral4_test_config().rope_parameters
    # dim 8 over an original context of 32: pair 0 turns 5 times over it
    # (kept), pairs 1-3 less than once (interpolated by the factor 8)
    plain = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    want = np.array([plain[0]] + list(plain[1:] / 8.0))
    np.testing.assert_allclose(yarn_inv_freq(8, rp_), want, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.yarn_frequencies(8, rp_)),
                               want, rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(ref.yarn_frequencies(8, rp_, "yarn_off")), plain,
        rtol=1e-6)
    # the published row: the ramp runs between pairs 12 and 25 of 32
    pub = {"rope_theta": 10000, "factor": 128, "beta_fast": 32,
           "beta_slow": 1, "original_max_position_embeddings": 8192}
    full = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    got = yarn_inv_freq(64, pub)
    np.testing.assert_allclose(got[:13], full[:13], rtol=1e-6)
    np.testing.assert_allclose(got[25:], full[25:] / 128, rtol=1e-6)
    assert np.all(got[13:25] < full[13:25])
    assert np.all(got[13:25] > full[13:25] / 128)
    assert np.all(np.diff(got) < 0)
    np.testing.assert_allclose(np.asarray(ref.yarn_frequencies(64, pub)),
                               got, rtol=1e-5)
    # the interleave: pairs (2j, 2j + 1) turn by pos * f_j; the program
    # keeps them at (j, j + D/2), the reference in place
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, 5, 2, 8)), jnp.float32)
    pos = jnp.asarray([0, 3, 31, 32, 77])
    inv = yarn_inv_freq(8, rp_)
    mine = np.asarray(rope_interleaved(x, pos, inv))[0]
    theirs = np.asarray(ref._rope_pairs(x[0], pos, jnp.asarray(inv)))
    np.testing.assert_allclose(
        mine, np.concatenate([theirs[..., 0::2], theirs[..., 1::2]], -1),
        atol=1e-5)
    z = (np.asarray(x[0, 2, 0, 0::2]) + 1j * np.asarray(x[0, 2, 0, 1::2])) \
        * np.exp(1j * 31 * inv)
    np.testing.assert_allclose(theirs[2, 0, 0::2], z.real, atol=1e-5)
    np.testing.assert_allclose(theirs[2, 0, 1::2], z.imag, atol=1e-5)
    # a_t: 1 exactly inside the original context, 1 + 0.1 ln(1 + t // 32)
    a = np.asarray(query_scale(jnp.asarray([0, 31, 32, 63, 64, 200]), rp_))
    np.testing.assert_allclose(
        a, [1, 1, 1 + 0.1 * math.log(2), 1 + 0.1 * math.log(2),
            1 + 0.1 * math.log(3), 1 + 0.1 * math.log(7)], rtol=1e-6)
    assert a[0] == 1.0 and a[1] == 1.0
    np.testing.assert_allclose(
        np.asarray(ref.position_scale(jnp.asarray([31, 32, 200]), rp_)),
        a[[1, 2, 5]], rtol=1e-6)


@pytest.fixture(scope="module")
def served(tiny):
    """Tokens the engine served past the original context and the logits
    rows it sampled them from, by request."""
    model, cfg = tiny
    prompts = _prompts(cfg, (40, 67), seed=13)
    outs, logits = _served_logits(_engine(model), prompts,
                                  SamplingParams(max_new_tokens=20))
    return [(o, len(p), np.stack(lg))
            for o, p, lg in zip(outs, prompts, logits)]


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_each_fault_of_the_reference_fails_the_comparison(tiny, served,
                                                          fault):
    """The served logits pass the sound reference at LOGIT_TOL and fail
    the reference computed wrongly on purpose by ten times it: a lower
    precision, a dropped expert, a cache row without its positional part,
    a_t = 1 past the original context, the plain frequencies."""
    model, cfg = tiny

    def worst(**kw):
        return max(np.abs(lg - _ref_logits(model, cfg, o, **kw)[n - 1:-1]
                          ).max() for o, n, lg in served)

    assert worst() <= LOGIT_TOL < 10 * LOGIT_TOL < worst(fault=fault)


# -- (e') the reference's ties ----------------------------------------------------

TEST_TIE = 0.05      # the tiny router's scores lie further apart than TIE


def _tied(model, cfg, seq):
    """(layer, position, gap) of the main path's rows whose last selected
    and first left-out scores lie within TEST_TIE, closest first."""
    found, route = [], ref._route

    def spy(m, *a, **kw):
        out = route(m, *a, **kw)
        if m.shape[0] == len(seq):                  # the main path's rows
            found.append(np.asarray(out[-1]))
        return out

    ref._route = spy
    try:
        _ref_logits(model, cfg, seq)
    finally:
        ref._route = route
    return sorted((float(g), l, p) for l, gaps in enumerate(found)
                  for p, g in enumerate(gaps) if g <= TEST_TIE)


def _turned_over(model, cfg, seq, layer, position):
    """The main path's logits with ONE selection taken the other way: the
    row `position` of layer `layer` gets the first score left out in place
    of the last selected."""
    calls, route = [], ref._route

    def turn(m, *a, **kw):
        sel, w, other, other_w, gap = route(m, *a, **kw)
        calls.append(None)
        if len(calls) - 1 == layer:
            sel = sel.at[position].set(other[position])
            w = w.at[position].set(other_w[position])
        return sel, w, other, other_w, gap

    ref._route = turn
    try:
        return _ref_logits(model, cfg, seq)
    finally:
        ref._route = route


def test_a_selection_turned_over_at_a_tie_reads_as_sound(tiny, monkeypatch):
    """A program that takes a tie the other way - one row, one layer, as
    rounding does - serves that row's token from the branch's logits: far
    under the main path's largest, and AT the largest of the position's
    branches.  A gap wider than the tie stays a miss."""
    model, cfg = tiny
    monkeypatch.setattr(ref, "SPAWN", 2)    # room for every tie of 90 rows
    params, conf = ref.params_from_model(model), _cfg_dict(cfg)
    seq = np.random.default_rng(21).integers(0, cfg.vocab_size, 90)
    ties = _tied(model, cfg, seq)
    assert len(ties) >= 4
    moved = 0
    for gap, layer, position in ties[:16]:
        picks = _turned_over(model, cfg, seq, layer, position).argmax(-1)
        alone, _ = ref.choice_margins(params, seq, picks, conf, tie=0.0)
        least, _ = ref.choice_margins(params, seq, picks, conf,
                                      tie=TEST_TIE)
        narrow, _ = ref.choice_margins(params, seq, picks, conf,
                                       tie=gap / 2)
        assert float(least[position]) <= LOGIT_TOL
        assert np.all(np.asarray(least) <= np.asarray(alone) + 1e-6)
        np.testing.assert_allclose(narrow[position], alone[position],
                                   atol=1e-6)
        moved += float(alone[position]) > 10 * LOGIT_TOL
    assert moved >= 2       # the turn moved the argmax: the test has teeth


def test_margins_without_ties_are_the_main_paths(tiny):
    """`greedy_margins` at tie 0 is `_margins` of `logits`, and at TIE it
    is never above it; a fault of the reference still fails the served
    tokens (the branches are computed under the fault too)."""
    model, cfg = tiny
    params, conf = ref.params_from_model(model), _cfg_dict(cfg)
    rows = np.random.default_rng(22).integers(0, cfg.vocab_size, (2, 70))
    plain, spread = ref.greedy_margins(params, rows, conf, tie=0.0)
    for row, got, sd in zip(rows, plain, spread):
        want, want_sd = ref._margins(
            ref.logits(params, jnp.asarray(row), conf), jnp.asarray(row))
        np.testing.assert_allclose(got, want, atol=1e-6)
        np.testing.assert_allclose(sd, want_sd, atol=1e-6)
    with_ties, _ = ref.greedy_margins(params, rows, conf, tie=TEST_TIE)
    assert np.all(with_ties <= plain + 1e-6) and np.any(with_ties < plain)
    greedy = np.concatenate([rows[0][:40], np.zeros(30, rows.dtype)])
    for p in range(39, 69):
        greedy[p + 1] = _ref_logits(model, cfg, greedy[:p + 1])[-1].argmax()
    sound, _ = ref.greedy_margins(params, greedy[None], conf, tie=TEST_TIE)
    wrong, _ = ref.greedy_margins(params, greedy[None], conf, tie=TEST_TIE,
                                  fault="no_rope_key")
    assert sound[0, 39:].max() <= 1e-5 < 0.05 < wrong[0, 39:].max()


# -- (f) the latent decode kernel, in interpret mode ---------------------------

@pytest.fixture
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    yield
    po.reset_attention_path_counts()


def _latent_case(rng, dt, b, h, dk, dv, bs, nb, lens, maxb):
    lanes = latent_pool_lanes(dk)
    pool = jnp.asarray(rng.normal(size=(nb, bs, lanes)), dt).at[
        :, :, dk:].set(0)
    lens = np.asarray(lens, np.int32)
    tbl = np.full((b, maxb), nb, np.int32)
    slots = np.full((b, 1), nb * bs, np.int32)
    perm, at = rng.permutation(nb), 0
    for i, n in enumerate(lens):
        k = -(-int(n) // bs)
        tbl[i, :k] = perm[at:at + k]
        at += k
        if n:
            slots[i, 0] = tbl[i, (n - 1) // bs] * bs + (n - 1) % bs
    q = jnp.asarray(rng.normal(size=(b, 1, h, dk)), dt)
    new = jnp.asarray(rng.normal(size=(b, 1, dk)), dt)
    return q, new, pool, tbl, np.maximum(lens - 1, 0), lens, slots


_M4 = (jnp.float32, 32, 320, 256, 64, 2e-5)        # the cell's geometry
_T = rp._LATENT_TILE_TOKENS     # a tile of the stream, in rows


@pytest.mark.parametrize("dt,h,dk,dv,bs,tol,lens,maxb,drop", [
    (jnp.float32, 32, 320, 256, 64, 2e-5, None, 12, ()),
    (jnp.bfloat16, 32, 320, 256, 64, 2e-2, None, 12, ()),
    (jnp.float32, 4, 192, 128, 16, 2e-5, None, 12, ()),
    (jnp.bfloat16, 12, 144, 128, 32, 2e-2, None, 12, ()),
    # the stream's edges at the cell's geometry; the rows streamed are a
    # row's length less its new one
    _M4 + ([0, 1, 2], 4, ()),
    _M4 + ([_T + 1, _T + 2, 2 * _T + 1, 2 * _T + 2], 2 * _T // 64 + 1, ()),
    _M4 + ([4 * _T + 1, 5 * _T + 1, 3 * _T - 40], 5 * _T // 64 + 1, ()),
    _M4 + ([2560, 2559, 2496], 40, ()),           # the table's full maxb
    _M4 + ([5, 300, 0, 70], 8, (0, 1)),           # slots out of range
    _M4 + ([1, 3 * _T + 200, 3, _T - 100, 0, 64], 3 * _T // 64 + 4, ())],
    ids=["mistral4-f32", "mistral4-bf16", "h4-bs16", "h12-bs32",
         "len-0-1-2", "tile-edge", "ring-remainder", "full-table",
         "padding-slot", "ragged-mix"])
def test_latent_kernel_against_the_fallback(_interpret_mode, dt, h, dk, dv,
                                            bs, tol, lens, maxb, drop):
    """The published geometry (32 heads over a 256 + 64 row in 384 lanes,
    blocks of 64) and two others: rows of 1 token, a part block, an empty
    (padding) row; then the stream's edges at the published geometry: rows
    of 0-2 tokens, streams that end on a tile's edge and one row past it,
    4 and 5 tiles in a ring of 3, a row that fills its table, rows whose
    slot lies out of range (their block untouched) and rows of very
    different lengths in one call.  The pool the kernel hands back is the
    fallback's bit for bit; the outputs agree to the online softmax's
    reordering."""
    rng = np.random.default_rng(5)
    lens = lens or [1, bs + 1, 9 * bs + 3, 0, 4 * bs, 5 * bs + 7]
    b = len(lens)
    nb = max(40, b * maxb)
    args = _latent_case(rng, dt, b, h, dk, dv, bs, nb, lens, maxb)
    q, new, pool, tbl, pos0, lens, slots = args
    for i, bad in zip(drop, (-1, nb * bs + 7)):
        slots[i, 0] = bad
    out, pool2 = rp.ragged_latent_attention_arrays(
        q, new, pool, tbl, pos0, lens, slots, dv, 0.09)
    assert po.attention_path_counts() == {
        "ragged_kernel": 1, "ragged_kernel:latent_products": 1}
    want_pool = latent_cache_update_arrays(pool, new, slots)
    want = latent_paged_attention_arrays(q, want_pool, tbl, pos0, dv, 0.09)
    np.testing.assert_array_equal(np.asarray(pool2, np.float32),
                                  np.asarray(want_pool, np.float32))
    # every row but the slots written is the pool handed in
    kept = np.ones(nb * bs, bool)
    kept[slots[(slots >= 0) & (slots < nb * bs)]] = False
    np.testing.assert_array_equal(
        np.asarray(pool2, np.float32).reshape(nb * bs, -1)[kept],
        np.asarray(pool, np.float32).reshape(nb * bs, -1)[kept])
    assert out.shape == (b, 1, h, dv)
    real = lens > 0
    np.testing.assert_allclose(np.asarray(out, np.float32)[real],
                               np.asarray(want, np.float32)[real],
                               atol=tol, rtol=0)
    assert not np.asarray(out, np.float32)[~real].any()


def test_latent_gate_counts_why_it_falls_back(_interpret_mode):
    rng = np.random.default_rng(6)
    for dk, dv, bs, c, why in [(320, 256, 64, 2, "chunk_gt_1"),
                               (32, 24, 16, 1, "latent_geometry"),
                               (320, 256, 4, 1, "block_size")]:
        po.reset_attention_path_counts()
        q, new, pool, tbl, pos0, lens, slots = _latent_case(
            rng, jnp.float32, 2, 4, dk, dv, bs, 8, [3, 5], 4)
        if c > 1:
            q = jnp.concatenate([q] * c, axis=1)
            new = jnp.concatenate([new] * c, axis=1)
            slots = np.concatenate([slots, slots + 1], axis=1)
            lens = lens + 1
        rp.ragged_latent_attention_arrays(q, new, pool, tbl, pos0, lens,
                                          slots, dv, 0.1)
        assert po.attention_path_counts() == {f"ragged_fallback:{why}": 1}


def test_engine_takes_both_kernels_at_lane_tile_widths(_interpret_mode):
    """A model whose latent is whole lane tiles (128 + 64 in 256 lanes):
    the prefill takes the flash kernel over the expanded heads, decode the
    latent kernel, and the tokens are the fallback engine's."""
    cfg = mistral4_test_config(
        hidden_size=128, num_attention_heads=2, num_key_value_heads=2,
        kv_lora_rank=128, q_lora_rank=32, qk_nope_head_dim=64,
        qk_rope_head_dim=64, qk_head_dim=128, v_head_dim=128, head_dim=128,
        num_hidden_layers=2, n_routed_experts=4)
    model = _seeded(cfg, seed=2)
    prompts = [list(np.random.default_rng(8).integers(0, 96, 128))]
    sp = SamplingParams(max_new_tokens=4)
    kw = dict(block_size=16, max_num_seqs=2, max_model_len=256)
    got = LLMEngine(model, EngineConfig(**kw)).generate(prompts, sp)
    paths = po.attention_path_counts()
    assert paths.get("attn_kernel", 0) + sum(
        v for k, v in paths.items() if k.startswith("attn_kernel")) > 0
    assert paths["ragged_kernel:latent_products"] >= 1
    assert not any("fallback" in k for k in paths)
    assert _margins(model, cfg, got[0], 128).max() <= NEAR_TIE


def test_grouped_products_in_chunks_equal_one_run(monkeypatch):
    """Past `_CHUNK_ROWS` sorted pairs the expert layer multiplies a
    chunk at a time, each chunk with its part of every expert's group:
    the result is the one-run result, whatever the chunk, a part chunk at
    the end included, in both tiers of rows."""
    from paddle_tpu.parallel import moe

    rng = np.random.default_rng(9)
    t, h, im, n, e = 700, 32, 16, 6, 8
    m = jnp.asarray(rng.standard_normal((t, h)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((h, e)), jnp.float32)
    experts = tuple(jnp.asarray(0.2 * rng.standard_normal(s), jnp.float32)
                    for s in ((n, h, im), (n, h, im), (n, im, h)))
    bias = jnp.zeros((e,), jnp.float32)

    def layer(first, held):
        return held_experts_arrays(
            m, router, bias, tuple(w[:held] for w in experts), first, held,
            2, 1.0)

    for first, held in ((0, 6), (2, 1)):     # the whole tier, the quarter
        want, want_stats = layer(first, held)
        for rows in (256, 300, 1400):
            monkeypatch.setattr(moe, "_CHUNK_ROWS", rows)
            got, stats = layer(first, held)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_array_equal(np.asarray(stats),
                                          np.asarray(want_stats))
        monkeypatch.undo()


@pytest.mark.parametrize("scenario", [
    "tokens_and_keys", "eos_mid_flight", "cancel_and_deadline",
    "forced_preemption"])
def test_a_step_in_flight_equals_the_settled_engine(tiny, scenario):
    """ISSUE 35 over mistral4's latent group: the scenarios of
    tests/_step_in_flight.py (tokens, keys at export, pools after a
    cancel, a deadline, an eviction) against the same engine with every
    step settled."""
    import _step_in_flight as sif

    model, cfg = tiny
    small = dict(num_blocks=12) if scenario == "forced_preemption" else {}
    monitor.enable(True)
    try:
        getattr(sif, "check_" + scenario)(
            lambda: _engine(model, max_num_seqs=3, **small), cfg.vocab_size)
    finally:
        monitor.refresh()
