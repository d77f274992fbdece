"""lfm2_moe through `LLMEngine` at a small size on the CPU (hidden 64, 4
query / 2 K/V heads of 16, block 4, 1 dense + 6 expert layers of which 2
attention and 5 gated short convolutions, 8 experts top-2), seeded weights,
against the plain reference `benchmark/lib/reference_lfm2.py`; the state
group's allocator beside the K/V group's; the expert layer's shares; the
decode kernel at 32 query heads over 8 K/V heads of 64 lanes and the flash
forward at the same heads, in interpret mode; and what the engine refuses.
Nothing here is a measurement.
"""
import dataclasses
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
from benchmark.lib import reference_lfm2 as ref  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.models import (Lfm2MoeForCausalLM, StateSpec,  # noqa: E402
                               lfm2_test_config)
from paddle_tpu.models.lfm2 import short_conv  # noqa: E402
from paddle_tpu.ops import pallas_ops as po  # noqa: E402
from paddle_tpu.ops import ragged_paged_attention as rp  # noqa: E402
from paddle_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention_arrays, paged_cache_update_arrays)
from paddle_tpu.parallel.moe import held_experts_arrays  # noqa: E402
from paddle_tpu.serving import EngineConfig, LLMEngine  # noqa: E402
from paddle_tpu.serving.kv_cache import (BlockAllocatorError,  # noqa: E402
                                         BlockKVCache, CacheGroups,
                                         StateCache)
from paddle_tpu.serving.scheduler import SamplingParams  # noqa: E402
from test_afmoe_serving import _ragged_case  # noqa: E402

BS = 4
# float32 on both sides: the orders of the sums differ (1e-4 logits at
# most); a wrong state, block, mask or expert moves a logit by tenths
NEAR_TIE = 1e-3


def _seeded(cfg, seed=0):
    """A model of `cfg` with weights from `seed`: matrices N(0, 0.08), the
    convolution's taps N(0, 0.5) (every tap must show), norm scales near 1
    (not exactly: a forgotten norm must show)."""
    model = Lfm2MoeForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for n, p in model.named_parameters():
        if "norm" in n:
            val = 1 + 0.1 * rng.standard_normal(p.shape)
        elif "expert_bias" in n:
            continue
        else:
            std = 0.5 if n.startswith("conv_w") else 0.08
            val = std * rng.standard_normal(p.shape)
        p._data = jnp.asarray(val, p._data.dtype)
    return model


def _cfg_dict(cfg):
    d = dataclasses.asdict(cfg)
    d["harness"] = {"kwargs": {"first_expert": cfg.first_expert,
                               "router_experts": cfg.router_experts}}
    return d


@pytest.fixture(scope="module")
def tiny():
    cfg = lfm2_test_config()
    return _seeded(cfg), cfg


def _margins(model, cfg, seq, prompt_len, **kw):
    """How far under each position's largest reference logit the served
    tokens' logits lie."""
    ids = jnp.asarray(seq)
    margins, _ = ref._margins(
        ref.logits(ref.params_from_model(model), ids, _cfg_dict(cfg), **kw),
        ids)
    return np.asarray(margins)[prompt_len - 1:]


def _engine(model, **kw):
    base = dict(block_size=BS, max_num_seqs=4, max_model_len=64)
    base.update(kw)
    return LLMEngine(model, EngineConfig(**base))


def _prompts(cfg, lens, seed=7):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab_size, n)) for n in lens]


# -- (a) the model and the engine against the reference -----------------------

def test_forward_matches_reference_logits(tiny):
    """Whole sequences, no cache, no state: every logit.  Tolerance 2e-4
    absolute on logits of standard deviation ~0.6 (float32 on both sides,
    XLA's default CPU matmul against "highest")."""
    model, cfg = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 27))
    got = np.asarray(model(jnp.asarray(ids))._data)
    params = ref.params_from_model(model)
    for row, g in zip(ids, got):
        want = np.asarray(ref.logits(params, jnp.asarray(row),
                                     _cfg_dict(cfg)))
        assert want.std() > 0.3
        np.testing.assert_allclose(g, want, atol=2e-4, rtol=0)


@pytest.mark.parametrize("lens,rows", [
    ((5, 13, 21), 4), ((3, 8, 9, 31), 4), ((1, 2), 4), ((6,), 2)],
    ids=["mixed", "block-edges", "shorter-than-the-taps", "padded"])
def test_engine_decodes_what_the_reference_ranks_first(tiny, lens, rows):
    """Prefill, then decode through BOTH caches - paged K/V for the two
    attention layers, a state slot for the five convolutions - a mixed
    batch, fewer rows than the program's (padding rows write the dropped
    slot), prompts shorter than the convolution's three taps: every served
    token is the reference's argmax over its full forward, up to float32
    near-ties."""
    model, cfg = tiny
    prompts = _prompts(cfg, lens)
    eng = _engine(model, max_num_seqs=rows)
    assert list(eng.caches) == ["full"] and list(eng.states) == ["conv"]
    assert eng.cache.num_layers == 2 and eng.states["conv"].num_layers == 5
    assert eng.states["conv"].state[0].shape == (rows + 1, 2, 64)
    assert isinstance(eng.kv, CacheGroups)
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=12))
    for p, o in zip(prompts, outs):
        assert len(o) == len(p) + 12
        assert _margins(model, cfg, o, len(p)).max() <= NEAR_TIE
    assert eng.cache.blocks_in_use == 0
    assert eng.states["conv"].slots_in_use == 0


@pytest.mark.parametrize("budget", [8, 5, 1], ids=lambda b: f"chunks-of-{b}")
def test_chunked_prefill_carries_the_state_across_chunks(tiny, budget):
    """A prompt of 23 fed in chunks under `max_num_batched_tokens`: each
    continuation reads the row's slot, the first included (zeroed at
    admission), and writes it back; chunks of 1 make every position a
    chunk of its own."""
    model, cfg = tiny
    prompt, = _prompts(cfg, (23,), seed=5)
    eng = _engine(model, max_num_seqs=2, max_num_batched_tokens=budget)
    out, = eng.generate([prompt], SamplingParams(max_new_tokens=6))
    assert _margins(model, cfg, out, len(prompt)).max() <= NEAR_TIE


def test_preemption_swaps_both_caches_and_the_tokens_do_not_move(tiny):
    """A K/V pool too small for three long rows: the scheduler evicts over
    BOTH groups, swaps back in, and the tokens are those of an engine that
    never preempted."""
    model, cfg = tiny
    prompts = _prompts(cfg, (14, 15, 13), seed=3)
    sp = SamplingParams(max_new_tokens=14)
    want = _engine(model).generate(prompts, sp)
    tight = _engine(model, num_blocks=16)
    swaps = monitor.counter("serving/state_swaps")
    before = {d: swaps.labels(dir=d).value for d in ("out", "in")}
    got = tight.generate(prompts, sp)
    assert tight.scheduler.num_evictions > 0 < tight.scheduler.num_swap_ins
    for d, n in (("out", tight.scheduler.num_evictions),
                 ("in", tight.scheduler.num_swap_ins)):
        assert swaps.labels(dir=d).value - before[d] == n
    for w, g, p in zip(want, got, prompts):
        np.testing.assert_array_equal(w, g)
        assert _margins(model, cfg, g, len(p)).max() <= NEAR_TIE


@pytest.mark.parametrize("scenario", [
    "tokens_and_keys", "eos_mid_flight", "cancel_and_deadline",
    "forced_preemption"])
def test_a_step_in_flight_equals_the_settled_engine(tiny, scenario):
    """ISSUE 35 over lfm2's state slots: the scenarios of
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


def test_export_and_adopt_carry_kv_and_state_bit_exactly(tiny):
    """A request exported mid-decode ships its K/V blocks AND its state
    rows as they stood in the exporter's pools, and decodes on in another
    engine to the tokens of an engine it never left."""
    model, cfg = tiny
    prompt, other = _prompts(cfg, (11, 6), seed=9)
    sp = SamplingParams(max_new_tokens=10)
    want, = _engine(model).generate([prompt], sp)
    src, dst = _engine(model), _engine(model)
    rid = src.add_request(prompt, sp)
    src.add_request(other, sp)            # so the slots and blocks differ
    for _ in range(5):
        src.step()
    conv = src.states["conv"]
    slot = conv.slot_of(rid)
    state_was = [np.asarray(s[slot]) for s in conv.state]
    table = src.cache.block_table(rid)
    k_was = [np.asarray(k[np.asarray(table)]) for k in src.cache.k_blocks]
    handoff = src.export_request(rid)
    assert rid not in conv._tables and rid not in src.cache._tables
    for was, shipped in zip(state_was,
                            handoff["kv"]["groups"]["conv"]["state"]):
        assert np.abs(was).max() > 0
        np.testing.assert_array_equal(was, shipped)
    for was, shipped in zip(k_was, handoff["kv"]["groups"]["full"]["k"]):
        np.testing.assert_array_equal(was, shipped)
    dst.add_request(other, sp)            # another slot order over there
    dst.step()
    new = dst.adopt_request(handoff["prompt_ids"], handoff["params"],
                            handoff["output_ids"], handoff["key"],
                            handoff["kv"])
    while dst.has_unfinished():
        dst.step()
    np.testing.assert_array_equal(dst.request_output(new), want)


def test_fork_copies_the_state(tiny):
    """A forked child starts from a copy of its parent's state slot (and
    shares its K/V blocks): it decodes the parent's own continuation."""
    model, cfg = tiny
    prompt, = _prompts(cfg, (9,), seed=11)
    sp = SamplingParams(max_new_tokens=8)
    eng = _engine(model)
    rid = eng.add_request(prompt, sp)
    for _ in range(3):
        eng.step()
    kid = eng.fork_request(rid, SamplingParams(max_new_tokens=5))
    conv = eng.states["conv"]
    assert conv.slot_of(kid) != conv.slot_of(rid)
    for s in conv.state:
        np.testing.assert_array_equal(np.asarray(s[conv.slot_of(kid)]),
                                      np.asarray(s[conv.slot_of(rid)]))
    while eng.has_unfinished():
        eng.step()
    parent, child = eng.request_output(rid), eng.request_output(kid)
    np.testing.assert_array_equal(child[:len(parent)][len(prompt) + 3:],
                                  parent[len(prompt) + 3:len(child)])
    assert _margins(model, cfg, child, len(prompt) + 3).max() <= NEAR_TIE


# -- (b) the state group's allocator ------------------------------------------

def _groups(blocks=8, slots=2):
    full = BlockKVCache(1, blocks, BS, 2, 16)
    conv = StateCache(2, slots, (2, 8), jnp.float32, name="conv")
    return full, conv, CacheGroups({"full": full, "conv": conv})


def test_a_slot_is_zeroed_on_reuse_and_costs_nothing_to_grow():
    _, conv, groups = _groups()
    groups.allocate("a", 3)
    slot = conv.slot_of("a")
    conv.state = [s.at[slot].set(7.0) for s in conv.state]
    for length in range(4, 20):           # one slot whatever the length
        assert groups.can_grow_to("a", length)
        groups.grow_to("a", length)
    assert conv.slots_in_use == 1 and conv.slot_of("a") == slot
    groups.free("a")
    assert conv.slots_in_use == 0
    groups.allocate("b", 5)
    assert conv.slot_of("b") == slot      # LIFO: the slot just given back
    for s in conv.state:
        assert not np.asarray(s[slot]).any()
    assert conv.state[0].shape == (3, 2, 8)       # 2 slots + the dropped


@pytest.mark.parametrize("short", ["state", "kv"])
def test_groups_allocate_all_or_none(short):
    """Whichever group is short, the other gives nothing."""
    full, conv, groups = _groups(blocks=4, slots=1 if short == "state"
                                 else 2)
    groups.allocate("a", 6)                       # 2 of 4 blocks, 1 slot
    need = 6 if short == "state" else 12          # 2 blocks fit, 3 do not
    assert full.can_allocate(need) == (short == "state")
    assert conv.can_allocate(need) == (short == "kv")
    assert not groups.can_allocate(need)
    with pytest.raises(BlockAllocatorError):
        groups.allocate("b", need)
    assert "b" not in full._tables and "b" not in conv._tables
    assert full.blocks_in_use == 2 and conv.slots_in_use == 1
    assert groups.num_free_blocks == 2            # K/V blocks, not slots


def test_groups_swap_kv_and_state_bit_exactly():
    full, conv, groups = _groups()
    groups.allocate("a", 10)
    rng = np.random.default_rng(0)
    full.k_blocks = [jnp.asarray(rng.standard_normal(k.shape), k.dtype)
                     for k in full.k_blocks]
    conv.state = [jnp.asarray(rng.standard_normal(s.shape), s.dtype)
                  for s in conv.state]
    k_was = np.asarray(full.k_blocks[0][np.asarray(full.block_table("a"))])
    s_was = [np.asarray(s[conv.slot_of("a")]) for s in conv.state]
    saved = groups.swap_out("a")
    assert full.blocks_in_use == 0 and conv.slots_in_use == 0
    groups.allocate("other", 5)                   # the ids and slots move
    assert groups.can_swap_in(saved)
    groups.swap_in("a", saved)
    assert conv.slot_of("a") != conv.slot_of("other")
    np.testing.assert_array_equal(
        k_was, np.asarray(full.k_blocks[0][np.asarray(
            full.block_table("a"))]))
    for was, s in zip(s_was, conv.state):
        np.testing.assert_array_equal(was, np.asarray(s[conv.slot_of("a")]))
    # and a snapshot comes back into both groups or into neither
    saved = groups.swap_out("a")
    groups.allocate("third", 1)                   # the second slot is taken
    assert full.can_swap_in(saved["groups"]["full"])
    assert not groups.can_swap_in(saved)
    with pytest.raises(BlockAllocatorError):
        groups.swap_in("a", saved)
    assert "a" not in full._tables and "a" not in conv._tables


def test_a_model_without_state_layers_builds_no_state_group():
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64, stacked_blocks=True))
    eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2))
    assert eng.states == {} and eng.kv is eng.cache
    assert eng._decode_inputs([], [], 2, 1)[5] == ()
    assert not any(isinstance(s, StateSpec) for s in eng.form.layer_specs)


# -- (c) what the engine refuses ----------------------------------------------

@pytest.mark.parametrize("option", [
    {"kv_cache_dtype": "int8"}, {"speculative_tokens": 2},
    {"enable_prefix_caching": True}], ids=lambda o: next(iter(o)))
def test_options_not_carried_to_the_family_raise_by_name(tiny, option):
    model, _ = tiny
    with pytest.raises(ValueError, match=next(iter(option))):
        LLMEngine(model, EngineConfig(block_size=BS, max_model_len=32,
                                      **option))


# -- (d) the expert layer's shares --------------------------------------------

def test_two_shares_of_four_add_up_to_the_uncut_layer_and_reference(tiny):
    """Experts 0-3 and 4-7 of 8, each share routing over all 8 with the
    family's 1e-6 under the weights: the two partial results add up to the
    uncut layer's, and the reference cut the same way adds up to the uncut
    reference's."""
    model, cfg = tiny
    params = ref.params_from_model(model)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 96, 19))
    whole = []
    ref.logits(params, ids, _cfg_dict(cfg), layer_out=whole)
    parts = []
    for first in (0, 4):
        part_cfg = _cfg_dict(lfm2_test_config(
            num_experts=4, router_experts=8, first_expert=first))
        sliced = dict(params)
        for n in ("exp_gate_w", "exp_up_w", "exp_down_w"):
            sliced[n] = [w[first:first + 4] for w in params[n]]
        out = []
        ref.logits(sliced, ids, part_cfg, layer_out=out)
        parts.append(np.asarray(out[1]))  # layer 1: the first expert layer
    # layer 1's input is the same in all three: layer 0 is dense
    np.testing.assert_allclose(parts[0] + parts[1], np.asarray(whole[1]),
                               atol=1e-5)
    # and the program's layer over the same tokens
    m = jnp.asarray(np.random.default_rng(3).standard_normal((24, 64)),
                    jnp.float32)
    e = {n: params[n][0] for n in ("router_w", "expert_bias", "exp_gate_w",
                                   "exp_up_w", "exp_down_w")}

    def share(first, n):
        y, stats = held_experts_arrays(
            m, e["router_w"], e["expert_bias"],
            tuple(e[k][first:first + n] for k in (
                "exp_gate_w", "exp_up_w", "exp_down_w")),
            first, n, 2, 1.0, norm_eps=1e-6)
        return np.asarray(y), [int(x) for x in stats]

    uncut, (held, absent, _, tokens) = share(0, 8)
    assert (held, absent, tokens) == (48, 0, 24)
    (lo, lo_stats), (hi, hi_stats) = share(0, 4), share(4, 4)
    assert lo_stats[0] + hi_stats[0] == 48
    np.testing.assert_allclose(lo + hi, uncut, atol=1e-5, rtol=1e-5)
    with jax.default_matmul_precision("highest"):
        want = ref._expert_mlp(m, e, range(8), 0, 2, 1.0, None)
    np.testing.assert_allclose(uncut, np.asarray(want), atol=1e-5,
                               rtol=1e-5)


def test_short_conv_is_the_shifted_sum_and_its_state_the_last_columns():
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((2, 5, 8)), jnp.float32)
    prev = jnp.asarray(rng.standard_normal((2, 2, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 3)), jnp.float32)
    c, state = short_conv(u, prev, w)
    full = np.concatenate([np.asarray(prev), np.asarray(u)], 1)
    want = sum(np.asarray(w)[:, k] * full[:, k:k + 5] for k in range(3))
    np.testing.assert_allclose(np.asarray(c), want, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(state), full[:, -2:])
    # one position at a time from that state: the same outputs
    for t in range(5):
        ct, prev = short_conv(u[:, t:t + 1], prev, w)
        np.testing.assert_allclose(np.asarray(ct[:, 0]), want[:, t],
                                   atol=1e-6)
    # a chunk shorter than the taps keeps a column of the old state
    _, state = short_conv(u[:, :1], jnp.zeros((2, 2, 8)), w)
    assert not np.asarray(state[:, 0]).any()
    np.testing.assert_array_equal(np.asarray(state[:, 1]),
                                  np.asarray(u[:, 0]))


# -- (e) each fault of the reference fails a comparison -----------------------

@pytest.fixture(scope="module")
def served(tiny):
    """Tokens the engine served, and their margins under the reference."""
    model, cfg = tiny
    prompts = _prompts(cfg, (9, 17), seed=13)
    outs = _engine(model).generate(prompts,
                                   SamplingParams(max_new_tokens=24))
    return [(o, len(p)) for o, p in zip(outs, prompts)]


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_each_fault_of_the_reference_fails_the_comparison(tiny, served,
                                                          fault):
    """The served tokens pass the sound reference at NEAR_TIE and fail the
    reference computed wrongly on purpose: a lower precision, a
    convolution without its history (a state never carried), a dropped
    expert, missing q/k norms, bfloat16 routing scores."""
    model, cfg = tiny
    sound = max(_margins(model, cfg, o, n).max() for o, n in served)
    wrong = max(_margins(model, cfg, o, n, fault=fault).max()
                for o, n in served)
    assert sound <= NEAR_TIE < wrong


# -- (f) the kernels at 32 query heads over 8 K/V heads of 64 lanes -----------

@pytest.fixture
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()


@pytest.mark.parametrize("heads,bs", [((32, 8), 64), ((32, 8), 16),
                                      ((8, 4), 16), ((4, 2), 16)],
                         ids=["32over8-bs64", "32over8-bs16", "8over4",
                              "4over2"])
def test_ragged_kernel_at_grouped_heads_of_64_lanes(_interpret_mode, heads,
                                                    bs):
    """The cell's call (32 over 8, pool rows of 512 lanes, blocks of 64)
    and smaller groups, against the XLA fallback: rows of one token, either
    side of a tile of 64 tokens, several tiles, a padding row, and a row
    WITHOUT a new token (its write slot dropped: every position comes from
    the pool).  Two K/V heads share a lane tile; each query row must read
    its own head's half.  Tolerance: the online softmax reorders float32
    sums."""
    hq, hkv = heads
    args = list(_ragged_case([1, 63, 64, 65, 150, 0, 70], hq=hq, hkv=hkv,
                             d=64, bs=bs))
    q, kn, vn, kb, vb, tables, pos0, lens, slots = args
    slots = np.asarray(slots).copy()
    slots[6, 0] = kb.shape[0] * bs                 # row 6: no new token
    args[8] = slots = jnp.asarray(slots)
    assert rp._ragged_kernel_ok(q, kb, 1, False)
    po.reset_attention_path_counts()
    out, k2, v2 = rp.ragged_paged_attention_arrays(*args)
    assert po.attention_path_counts() == {
        "ragged_kernel": 1, "ragged_kernel:head_products": 1}
    k2r = paged_cache_update_arrays(kb, kn, slots)
    v2r = paged_cache_update_arrays(vb, vn, slots)
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k2r))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v2r))
    want = paged_attention_arrays(q, k2r, v2r, tables, pos0)
    keep = [0, 1, 2, 3, 4, 6]
    np.testing.assert_allclose(np.asarray(out)[keep], np.asarray(want)[keep],
                               rtol=2e-6, atol=2e-6)
    assert not np.asarray(out)[5].any()            # the padding row


def test_ragged_gate_at_heads_of_64_lanes(_interpret_mode):
    """Grouped heads of 64 take the per-head products when a pair's 2 x G
    query heads fit the products' 8 rows; ungrouped heads of 64 keep the
    segment body; 8 query heads a K/V head of 64 fall back by name."""
    def gate(hq, hkv):
        q, _, _, kb, *_ = _ragged_case([9], hq=hq, hkv=hkv, d=64)
        return rp._ragged_kernel_ok(q, kb, 1, False)

    assert gate(32, 8) and gate(4, 2) and gate(6, 2)
    assert po.attention_path_counts() == {
        "ragged_kernel": 3, "ragged_kernel:head_products": 3}
    po.reset_attention_path_counts()
    assert gate(4, 4)
    assert po.attention_path_counts() == {
        "ragged_kernel": 1, "ragged_kernel:segment_products": 1}
    po.reset_attention_path_counts()
    assert not gate(16, 2)
    assert po.attention_path_counts() == {
        "ragged_fallback:grouped_head_dim": 1}


def test_flash_forward_at_32_over_8_heads_of_64(_interpret_mode):
    """The prefill's kernel at the cell's heads (S = 256, two blocks)."""
    rng = np.random.default_rng(0)
    f = lambda h: jnp.asarray(rng.standard_normal((1, 256, h, 64)),  # noqa
                              jnp.float32)
    q, k, v = f(32), f(8), f(8)
    got = po.flash_attention_arrays(q, k, v, is_causal=True)
    assert po.attention_path_counts() == {"attn_kernel:grouped": 1}
    want = po.mha_reference(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_engine_takes_both_kernels_at_heads_of_64(_interpret_mode):
    """Through the engine in interpret mode: 8 query heads over 2 K/V
    heads of 64 (one lane tile a pool row), blocks of 16.  Prefill counts
    the flash kernel, decode the ragged kernel's per-head products, no
    fallback; the tokens are the reference's."""
    cfg = lfm2_test_config(
        hidden_size=128, num_attention_heads=8, num_key_value_heads=2,
        head_dim=64, num_hidden_layers=3, num_dense_layers=1,
        layer_types=["conv", "full_attention", "conv"],
        max_position_embeddings=512)
    model = _seeded(cfg, seed=4)
    prompts = _prompts(cfg, (128, 128), seed=2)
    eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2,
                                        max_model_len=256))
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=4))
    counts = po.attention_path_counts()
    assert counts.get("attn_kernel:grouped") and \
        counts.get("ragged_kernel:head_products")
    assert not [k for k in counts if "_fallback:" in k], counts
    for p, o in zip(prompts, outs):
        assert _margins(model, cfg, o, len(p)).max() <= NEAR_TIE


# -- (g) spans and counters ---------------------------------------------------

def test_state_group_counters_and_scopes(tiny):
    model, cfg = tiny
    eng = _engine(model)

    def val(name, **labels):
        return monitor.snapshot().get(name, {}).get(
            ",".join(f"{k}={v}" for k, v in sorted(labels.items())), 0)

    pool = eng.states["conv"]
    assert val("serving/state_bytes", group="conv") == pool.pool_bytes \
        == 5 * 5 * 2 * 64 * 4
    steps0 = val("serving/state_slot_steps", group="conv")
    pairs0 = val("serving/moe_pairs", phase="decode", where="held")
    rid = eng.add_request(_prompts(cfg, (7,))[0],
                          SamplingParams(max_new_tokens=4))
    eng.step()
    assert val("serving/state_slots_in_use", group="conv") == 1
    while eng.has_unfinished():
        eng.step()
    eng.release_request(rid)
    # 3 decode steps of one row: a slot each, 6 expert layers x top-2 pairs
    assert val("serving/state_slot_steps", group="conv") - steps0 == 3
    assert val("serving/moe_pairs", phase="decode", where="held") \
        - pairs0 == 3 * 6 * 2
    assert val("serving/state_slots_in_use", group="conv") == 0
    # the scopes the trace's readers look for are in the decode program
    toks, pos0, lens, tables, slots, srows = eng._decode_inputs([], [], 4, 1)
    text = eng._get_ragged_exec(4, 1).lower(
        eng._param_arrays(), eng._kv_flat(), toks, pos0, lens, tables,
        slots, srows).as_text(debug_info=True)
    for scope in ("lfm2/conv", "lfm2/router", "lfm2/experts", "attn/full"):
        assert scope in text, scope
