"""afmoe through `LLMEngine` at a small size on the CPU (hidden 64, 4
query / 2 K/V heads of 16, window 8, block 4, 1 dense + 5 expert layers, 8
experts top-2 + shared), seeded weights, against the plain reference
`benchmark/lib/reference_afmoe.py`; the cache groups' allocator; the
expert layer's shares; both kernels in interpret mode with grouped heads
and a window; and what the engine refuses.  Nothing here is a measurement.
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
from benchmark.lib import reference_afmoe as ref  # noqa: E402
from paddle_tpu.models import (AfmoeForCausalLM, GPTConfig,  # noqa: E402
                               GPTForCausalLM, afmoe_test_config)
from paddle_tpu.models import afmoe as afmoe_mod  # noqa: E402
from paddle_tpu.ops import pallas_ops as po  # noqa: E402
from paddle_tpu.ops import ragged_paged_attention as rp  # noqa: E402
from paddle_tpu.ops.paged_attention import (  # noqa: E402
    paged_attention_arrays, paged_cache_update_arrays)
from paddle_tpu.parallel.moe import held_experts_arrays  # noqa: E402
from paddle_tpu.serving import EngineConfig, LLMEngine  # noqa: E402
from paddle_tpu.serving.kv_cache import (BlockAllocatorError,  # noqa: E402
                                         BlockKVCache, CacheGroups)
from paddle_tpu.serving.scheduler import SamplingParams  # noqa: E402

WINDOW, BS = 8, 4


def _seeded(cfg, seed=0):
    """A model of `cfg` with weights from `seed`: matrices N(0, 0.08),
    norm scales near 1 (not exactly: a forgotten norm must show)."""
    model = AfmoeForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for n, p in model.named_parameters():
        if "norm" in n:
            val = 1 + 0.1 * rng.standard_normal(p.shape)
        elif "expert_bias" in n:
            continue
        else:
            val = 0.08 * rng.standard_normal(p.shape)
        p._data = jnp.asarray(val, p._data.dtype)
    return model


def _cfg_dict(cfg):
    d = dataclasses.asdict(cfg)
    d["harness"] = {"kwargs": {"first_expert": cfg.first_expert,
                               "router_experts": cfg.router_experts}}
    return d


@pytest.fixture(scope="module")
def share():
    """Chip 1 of 4: experts 8..15 of 32."""
    cfg = afmoe_test_config(router_experts=32, first_expert=8)
    return _seeded(cfg), cfg


def _worst_margin(model, cfg, seq, prompt_len):
    """How far under each position's largest reference logit the served
    tokens' logits lie, at worst."""
    ids = jnp.asarray(seq)
    margins, _ = ref._margins(
        ref.logits(ref.params_from_model(model), ids, _cfg_dict(cfg)), ids)
    return float(np.asarray(margins)[prompt_len - 1:].max())


# -- (a) the engine against the reference -------------------------------------

def test_forward_matches_reference_logits(share):
    """Whole sequences, no cache: every logit.  Tolerance 2e-4 absolute on
    logits of standard deviation ~0.6: both sides are float32 here and
    differ by the order of their sums (XLA's default CPU matmul against
    "highest")."""
    model, cfg = share
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 27))
    got = np.asarray(model(jnp.asarray(ids))._data)
    params = ref.params_from_model(model)
    for row, g in zip(ids, got):
        want = np.asarray(ref.logits(params, jnp.asarray(row),
                                     _cfg_dict(cfg)))
        np.testing.assert_allclose(g, want, atol=2e-4, rtol=0)


@pytest.mark.parametrize("lens", [(5, 13, 21), (3, 8, 9, 31)],
                         ids=["past-window", "block-edges"])
def test_engine_decodes_what_the_reference_ranks_first(share, lens):
    """Prefill, then decode through the paged cache, a mixed batch whose
    contexts pass the window (8) and cross block edges (4): every served
    token is the reference's argmax, up to float32 near-ties (1e-3 logits;
    a wrong block, mask or expert moves a logit by tenths)."""
    model, cfg = share
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in lens]
    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=4,
                                        max_model_len=64))
    assert list(eng.caches) == ["full", "window"]
    assert eng.cache is eng.caches["full"] and eng.cache.num_layers == 1
    assert eng.caches["window"].num_layers == 5
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=12))
    for p, o in zip(prompts, outs):
        assert len(o) == len(p) + 12
        assert _worst_margin(model, cfg, o, len(p)) <= 1e-3
    assert all(k.blocks_in_use == 0 for k in eng.caches.values())


def test_preemption_and_swap_in_on_the_way(share):
    """A full group too small for three long rows: the scheduler evicts
    over BOTH groups, swaps back in, and the tokens are those of an engine
    that never preempted."""
    model, cfg = share
    rng = np.random.default_rng(3)
    prompts = [list(rng.integers(0, cfg.vocab_size, n)) for n in (14, 15, 13)]
    sp = SamplingParams(max_new_tokens=14)
    roomy = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=4,
                                          max_model_len=64))
    want = roomy.generate(prompts, sp)
    tight = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=4,
                                          max_model_len=64, num_blocks=16))
    got = tight.generate(prompts, sp)
    assert tight.scheduler.num_evictions > 0 < tight.scheduler.num_swap_ins
    for w, g, p in zip(want, got, prompts):
        np.testing.assert_array_equal(w, g)
        assert _worst_margin(model, cfg, g, len(p)) <= 1e-3


def test_chunked_prefill_through_the_window_group(share):
    """A prompt fed in chunks reads its earlier chunks from both pools
    through the paged fallback (C > 1), window mask included."""
    model, cfg = share
    prompt = list(np.random.default_rng(5).integers(0, cfg.vocab_size, 23))
    eng = LLMEngine(model, EngineConfig(
        block_size=BS, max_num_seqs=2, max_model_len=64,
        max_num_batched_tokens=8))
    out, = eng.generate([prompt], SamplingParams(max_new_tokens=6))
    assert _worst_margin(model, cfg, out, len(prompt)) <= 1e-3


# -- (b) (c) the expert layer -------------------------------------------------

def _expert_case(seed=0, t=24, h=32, i=16, e=16, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(0.3 * rng.standard_normal(s), dtype)  # noqa
    return dict(m=f(t, h), router_w=f(h, e), bias=jnp.zeros(e),
                gate=f(e, h, i), up=f(e, h, i), down=f(e, i, h))


def _dense_experts(c, top_k, scale, bias=None):
    """Every expert over every token, weighted: the uncut layer without
    its shared expert, the plain way."""
    p = {"router_w": c["router_w"],
         "expert_bias": c["bias"] if bias is None else bias}
    with jax.default_matmul_precision("highest"):
        sel, w = ref._route(c["m"], p["router_w"], p["expert_bias"],
                            top_k=top_k, route_scale=scale, fault=None)
        out = 0.0
        for e in range(c["router_w"].shape[1]):
            weight = jnp.where(sel == e, w, 0.0).sum(-1)
            out = out + ref._one_expert(c["m"], c["gate"][e], c["up"][e],
                                        c["down"][e], weight, fault=None)
    return np.asarray(out)


def test_shares_add_up_to_the_uncut_layer():
    """What the 4 shares give, each over its own 4 of 16 experts, adds up
    to the uncut layer (the shared expert is computed alike on every chip
    and counted once: it is outside `held_experts_arrays`)."""
    c = _expert_case()
    total, pairs = 0.0, 0
    for first in range(0, 16, 4):
        y, stats = held_experts_arrays(
            c["m"], c["router_w"], c["bias"],
            (c["gate"][first:first + 4], c["up"][first:first + 4],
             c["down"][first:first + 4]), first, 4, 2, 2.448)
        total = total + np.asarray(y)
        held, absent, touched, tokens = (int(x) for x in stats)
        assert held + absent == 2 * tokens == 48 and touched <= 4
        pairs += held
    assert pairs == 48                       # every pair is held somewhere
    np.testing.assert_allclose(total, _dense_experts(c, 2, 2.448),
                               atol=1e-5, rtol=1e-5)


def test_reference_shares_add_up_to_the_uncut_reference(share):
    """The same of the reference: its MLP term `f` of the first expert
    layer over the four shares, less the shared expert counted thrice too
    often, is the uncut reference's."""
    _, cfg = share
    uncut_cfg = afmoe_test_config(num_experts=32)
    uncut = _seeded(uncut_cfg)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 96, 19))
    params = ref.params_from_model(uncut)
    whole = []
    ref.logits(params, ids, _cfg_dict(uncut_cfg), layer_out=whole)
    parts = []
    for first in range(0, 32, 8):
        part_cfg = _cfg_dict(afmoe_test_config(router_experts=32,
                                               first_expert=first))
        sliced = dict(params)
        for n in ("exp_gate_w", "exp_up_w", "exp_down_w"):
            sliced[n] = [w[first:first + 8] for w in params[n]]
        out = []
        ref.logits(sliced, ids, part_cfg, layer_out=out)
        parts.append(out[1])            # layer 1: the first expert layer
    only_shared = []
    ref.logits(params, ids, _cfg_dict(uncut_cfg), held=range(0),
               layer_out=only_shared)
    total = sum(np.asarray(p) for p in parts) - 3 * np.asarray(only_shared[1])
    np.testing.assert_allclose(total, np.asarray(whole[1]), atol=1e-5)


@pytest.mark.parametrize("tokens", [24, 700], ids=["one-tier", "split"])
def test_no_pair_dropped_when_one_expert_takes_every_token(tokens):
    """A selection bias that sends every token to expert 5: its group
    holds all T rows (past `_SPLIT_ROWS` the quarter-size tier cannot and
    the full one runs), nothing is dropped, and the weights ignore the
    bias."""
    c = _expert_case(seed=1, t=tokens)
    bias = jnp.zeros(16).at[5].set(100.0)
    y, stats = held_experts_arrays(
        c["m"], c["router_w"], bias, (c["gate"][4:8], c["up"][4:8],
                                      c["down"][4:8]), 4, 4, 2, 1.0)
    held, absent, _, routed = (int(x) for x in stats)
    assert routed == tokens and held + absent == 2 * tokens
    assert held >= tokens                    # expert 5 alone has them all
    want = _dense_experts(
        {**c, **{k: c[k].at[:4].set(0).at[8:].set(0)
                 for k in ("gate", "up", "down")}}, 2, 1.0, bias=bias)
    np.testing.assert_allclose(np.asarray(y), want, atol=2e-5, rtol=1e-5)


def test_padding_rows_route_nowhere():
    c = _expert_case(seed=2, t=8)
    valid = jnp.asarray([True] * 5 + [False] * 3)
    y, stats = held_experts_arrays(
        c["m"], c["router_w"], c["bias"], (c["gate"], c["up"], c["down"]),
        0, 16, 2, 1.0, valid=valid)
    assert [int(x) for x in stats][::3] == [10, 5]   # held pairs, tokens
    assert not np.asarray(y)[5:].any()


def test_bf16_scores_move_the_selection():
    """Why the router's scores are float32: at the published width the
    4th and 5th of 256 scores are often closer than a bfloat16 rounding."""
    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.standard_normal((512, 256)), jnp.bfloat16)
    w = jnp.asarray(0.02 * rng.standard_normal((256, 256)), jnp.bfloat16)
    from paddle_tpu.parallel.moe import route_top_k

    sel, _ = route_top_k(m, w, jnp.zeros(256), 4, 1.0, 1e-20)
    low = jax.lax.top_k(jax.nn.sigmoid(
        (m @ w).astype(jnp.float32)), 4)[1]
    moved = (np.sort(np.asarray(sel), -1) != np.sort(np.asarray(low), -1)
             ).any(-1).mean()
    assert moved > 0.01


# -- (d) the cache groups -----------------------------------------------------

def _window_cache(blocks=12):
    return BlockKVCache(2, blocks, BS, 2, 16, window=WINDOW, name="window")


def test_window_group_is_bounded_and_releases_as_the_row_grows():
    cache = _window_cache()
    cache.allocate("a", 3)
    held_max = 0
    for length in range(4, 60):
        assert cache.can_grow_to("a", length)
        cache.grow_to("a", length)
        table = cache.block_table("a")
        live = [i for i in table if i < cache.num_blocks]
        held_max = max(held_max, len(live))
        assert len(table) == cache.blocks_needed(length)
        # everything the step's query (position length-1) sees is live
        for p in range(max(0, length - WINDOW), length):
            assert cache.slot("a", p) < cache.num_slots
        assert len(live) * BS <= WINDOW + BS
    assert held_max == WINDOW // BS + 1 and cache.released > 0
    assert cache.slot("a", 0) >= cache.num_slots      # points nowhere
    cache.free("a")
    assert cache.blocks_in_use == 0


def test_whole_prompt_longer_than_the_window_takes_its_tail_only():
    cache = _window_cache()
    assert cache.tail_start(30) == 20        # (30 + 1 - 8) // 4 * 4
    assert cache.can_allocate(30, tail_only=True)
    cache.allocate("a", 30, tail_only=True)
    table = cache.block_table("a")
    assert [i < cache.num_blocks for i in table] == [False] * 5 + [True] * 3
    full = BlockKVCache(1, 12, BS, 2, 16)
    assert full.tail_start(30) == 0
    full.allocate("a", 30, tail_only=True)
    assert len(full.block_table("a")) == 8


def test_groups_allocate_all_or_none_and_swap_bit_exactly():
    full = BlockKVCache(1, 8, BS, 2, 16)
    win = _window_cache(blocks=6)
    groups = CacheGroups({"full": full, "window": win})
    groups.allocate("a", 10)
    assert win.can_allocate(24, tail_only=True)
    assert not groups.can_allocate(24, tail_only=True)
    with pytest.raises(BlockAllocatorError):     # the full group is short
        groups.allocate("b", 24, tail_only=True)
    assert "b" not in full._tables and "b" not in win._tables
    for length in range(11, 22):
        groups.grow_to("a", length)
    rng = np.random.default_rng(0)
    for cache in (full, win):
        cache.k_blocks = [jnp.asarray(rng.standard_normal(k.shape),
                                      k.dtype) for k in cache.k_blocks]
        cache.v_blocks = [jnp.asarray(rng.standard_normal(v.shape),
                                      v.dtype) for v in cache.v_blocks]

    def content(cache):
        t = cache.block_table("a")
        return [(j, np.asarray(cache.k_blocks[l][i]),
                 np.asarray(cache.v_blocks[l][i]))
                for l in range(cache.num_layers)
                for j, i in enumerate(t) if i < cache.num_blocks]

    before = [content(full), content(win)]
    saved = groups.swap_out("a")
    assert full.blocks_in_use == win.blocks_in_use == 0
    groups.allocate("other", 5)              # the ids move
    assert groups.can_swap_in(saved)
    groups.swap_in("a", saved)
    for was, cache in zip(before, (full, win)):
        now = content(cache)
        assert [j for j, _, _ in now] == [j for j, _, _ in was]
        for (_, k0, v0), (_, k1, v1) in zip(was, now):
            np.testing.assert_array_equal(k0, k1)
            np.testing.assert_array_equal(v0, v1)
    groups.grow_to("a", 22)                  # and the row goes on


def test_window_group_refuses_what_it_does_not_carry():
    cache = _window_cache()
    cache.allocate("a", 6)
    for call in (lambda: cache.fork("a", "b"),
                 lambda: cache.truncate_to("a", 4),
                 lambda: cache.register_prefix("a", [b"k"], 4)):
        with pytest.raises(BlockAllocatorError, match="window group"):
            call()
    with pytest.raises(ValueError, match="int8"):
        BlockKVCache(1, 4, BS, 2, 16, kv_quant="int8", window=8)


# -- (e) the kernels, interpret mode ------------------------------------------

@pytest.fixture
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")


def _ragged_case(lens, hq=4, hkv=2, d=128, bs=8, seed=0):
    """Decode rows of the given lengths (0: a padding row) over pools of
    `hkv` heads; tables in scrambled order."""
    rng = np.random.default_rng(seed)
    b = len(lens)
    maxb = max(-(-n // bs) for n in lens) + 1
    nb = b * maxb + 1
    perm = rng.permutation(nb - 1)
    tables = np.full((b, maxb), nb, np.int32)
    slots = np.full((b, 1), nb * bs, np.int32)
    for r, n in enumerate(lens):
        for j in range(-(-n // bs)):
            tables[r, j] = perm[r * maxb + j]
        if n:
            slots[r, 0] = tables[r, (n - 1) // bs] * bs + (n - 1) % bs
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa
    lens = jnp.asarray(lens, jnp.int32)
    return (f(b, 1, hq, d), f(b, 1, hkv, d), f(b, 1, hkv, d),
            f(nb, bs, hkv * d), f(nb, bs, hkv * d), jnp.asarray(tables),
            jnp.maximum(lens - 1, 0), lens, jnp.asarray(slots))


@pytest.mark.parametrize("window", [None, 16, 5], ids=str)
def test_ragged_kernel_grouped_heads_and_window(_interpret_mode, window):
    """Lengths either side of the window (15, 16, 17), one of a single
    token, one of several blocks, and a padding row, against the XLA
    fallback.  Tolerance: the online softmax reorders float32 sums."""
    args = _ragged_case([1, 15, 16, 17, 41, 0])
    q, kn, vn, kb, vb, tables, pos0, lens, slots = args
    assert rp._ragged_kernel_ok(q, kb, 1, False, window)
    kw = {} if window is None else {"window": window}
    out, k2, v2 = rp.ragged_paged_attention_arrays(*args, **kw)
    k2r = paged_cache_update_arrays(kb, kn, slots)
    v2r = paged_cache_update_arrays(vb, vn, slots)
    np.testing.assert_array_equal(np.asarray(k2), np.asarray(k2r))
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v2r))
    want = paged_attention_arrays(q, k2r, v2r, tables, pos0, **kw)
    np.testing.assert_allclose(np.asarray(out[:5]), np.asarray(want[:5]),
                               rtol=2e-6, atol=2e-6)


def test_ragged_kernel_never_reads_behind_the_window(_interpret_mode):
    """Table entries wholly behind the window point nowhere (the window
    group gave those blocks back): same answer."""
    args = list(_ragged_case([41, 17]))
    want, _, _ = rp.ragged_paged_attention_arrays(*args, window=16)
    tables = np.asarray(args[5]).copy()
    nb = args[3].shape[0]
    tables[0, :(41 - 16) // 8] = nb
    tables[1, :(17 - 16) // 8] = nb
    args[5] = jnp.asarray(tables)
    got, _, _ = rp.ragged_paged_attention_arrays(*args, window=16)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    fallback = paged_attention_arrays(
        args[0], paged_cache_update_arrays(args[3], args[1], args[8]),
        paged_cache_update_arrays(args[4], args[2], args[8]), args[5],
        args[6], window=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(fallback),
                               rtol=2e-6, atol=2e-6)


def test_ragged_gate_counts_the_new_refusals(_interpret_mode, monkeypatch):
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    q, _, _, kb, *_ = _ragged_case([9])
    assert not rp._ragged_kernel_ok(q[:, :, :3], kb, 1, False)   # 3 over 2
    assert not rp._ragged_kernel_ok(q, kb, 1, False, window=0)
    assert not rp._ragged_kernel_ok(q, kb.astype(jnp.int8), 1, True)
    # heads of half a lane tile, 8 query heads each: a pair's 16 do not
    # fit the products' 8 rows (4 over 2 does, since PR 32)
    q64, _, _, kb64, *_ = _ragged_case([9], hq=16, d=64)
    assert not rp._ragged_kernel_ok(q64, kb64, 1, False)
    counts = po.attention_path_counts()
    assert counts["ragged_fallback:grouped_head_dim"] == 1
    assert counts["ragged_fallback:kv_head_groups"] == 1
    assert counts["ragged_fallback:window_lt_1"] == 1
    assert counts["ragged_fallback:quant_grouped_or_window"] == 1


@pytest.mark.parametrize("window", [None, 128, 100, 300], ids=str)
def test_flash_forward_grouped_heads_and_window(_interpret_mode, window,
                                                monkeypatch):
    """S = 256 in blocks of 128: a window of one block, one that cuts a
    block, one wider than the sequence, and none; 4 query heads over 2."""
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    rng = np.random.default_rng(0)
    f = lambda h: jnp.asarray(rng.standard_normal((2, 256, h, 64)),  # noqa
                              jnp.float32)
    q, k, v = f(4), f(2), f(2)
    got = po.flash_attention_arrays(q, k, v, is_causal=True, window=window)
    want = po.mha_reference(q, k, v, is_causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    name = "attn_kernel:grouped" + (":window" if window else "")
    assert po.attention_path_counts() == {name: 1}
    # and the reference's window is the definition: key j iff 0 <= i-j < w
    if window == 100:
        i, j = np.arange(256)[:, None], np.arange(256)[None]
        mask = (j <= i) & (i - j < 100)
        kk, vv = (np.repeat(np.asarray(a), 2, axis=2) for a in (k, v))
        s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), kk) / 8.0
        s = np.where(mask, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(
            np.asarray(want), np.einsum("bhqk,bkhd->bqhd", p, vv),
            rtol=2e-5, atol=2e-5)


def test_flash_gate_counts_the_new_refusals(_interpret_mode, monkeypatch):
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    z = lambda h: jnp.zeros((1, 128, h, 64), jnp.float32)  # noqa: E731
    with pytest.raises(ValueError, match="multiple of the K/V heads"):
        po.flash_attention_arrays(z(3), z(2), z(2), is_causal=True)
    po.flash_attention_arrays(z(2), z(2), z(2), is_causal=False, window=8)
    counts = po.attention_path_counts()
    assert counts == {"attn_fallback:kv_head_groups": 1,
                      "attn_fallback:window_not_causal": 1}
    with pytest.raises(ValueError, match="is_causal only"):
        po.flash_attention_arrays(z(4), z(2), z(2), is_causal=True,
                                  kv_lens=jnp.asarray([5]))


# -- (f) positions ------------------------------------------------------------

def test_sliding_layers_rotate_and_full_layers_do_not(share):
    """Other positions (doubled: a uniform shift is invisible to rotary
    positions) change a sliding layer's output and leave a full layer's
    bit-identical."""
    model, cfg = share
    form = model.serving_form()
    params = form.params()
    h = jnp.asarray(np.random.default_rng(0).standard_normal((1, 6, 64)),
                    jnp.float32)
    pos = jnp.arange(6, dtype=jnp.int32)[None]

    def attn(q, k, v):
        return po.mha_reference(q, k, v, is_causal=True), ()

    def run(l, p):
        return np.asarray(form.layer(l, params, h, p, attn)[0])

    full = cfg.layer_types.index(afmoe_mod.FULL)
    sliding = cfg.layer_types.index(afmoe_mod.SLIDING)
    np.testing.assert_array_equal(run(full, pos), run(full, 2 * pos))
    assert np.abs(run(sliding, pos) - run(sliding, 2 * pos)).max() > 1e-3
    assert [s.window for s in form.layer_specs] == [
        8 if t == afmoe_mod.SLIDING else None for t in cfg.layer_types]


# -- what the engine refuses --------------------------------------------------

@pytest.mark.parametrize("option", [
    {"kv_cache_dtype": "int8"}, {"speculative_tokens": 2},
    {"enable_prefix_caching": True}], ids=lambda o: next(iter(o)))
def test_options_not_carried_to_the_family_raise_by_name(share, option):
    model, _ = share
    with pytest.raises(ValueError, match=next(iter(option))):
        LLMEngine(model, EngineConfig(block_size=BS, max_model_len=32,
                                      **option))


def test_fork_over_a_window_group_raises(share):
    model, _ = share
    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=2,
                                        max_model_len=32))
    rid = eng.add_request([1, 2, 3, 4, 5], SamplingParams(max_new_tokens=4))
    eng.step()
    with pytest.raises(BlockAllocatorError, match="window group"):
        eng.fork_request(rid)
    eng.release_request(rid)


def test_default_pool_the_device_cannot_hold_is_refused(monkeypatch):
    """max_model_len unset: 262,144 positions a row.  Nothing is
    allocated: the refusal comes first and names both ways out."""
    model = AfmoeForCausalLM(afmoe_test_config(
        max_position_embeddings=262144))
    monkeypatch.setattr(LLMEngine, "_device_bytes",
                        staticmethod(lambda: 16 * 2 ** 30))
    made = []
    monkeypatch.setattr(BlockKVCache, "__init__",
                        lambda self, *a, **k: made.append(a))
    with pytest.raises(ValueError) as err:
        LLMEngine(model, EngineConfig(block_size=64, max_num_seqs=32768))
    assert "max_model_len" in str(err.value)
    assert "num_blocks" in str(err.value) and not made


def test_model_without_a_serving_form_is_refused():
    with pytest.raises(ValueError, match="serving form"):
        LLMEngine(type("Bare", (), {"cfg": None, "eval": lambda s: s})())
    with pytest.raises(ValueError, match="stacked_blocks"):
        LLMEngine(GPTForCausalLM(GPTConfig(
            vocab_size=64, hidden_size=32, num_hidden_layers=1,
            num_attention_heads=2, intermediate_size=64,
            max_position_embeddings=32, stacked_blocks=False)))


def test_gpt_is_one_group_that_allocates_as_before():
    model = GPTForCausalLM(GPTConfig(
        vocab_size=64, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64, stacked_blocks=True))
    eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2))
    assert list(eng.caches) == ["full"] and eng.kv is eng.cache
    assert eng.cache.num_blocks == 2 * 8 and eng.cache.window is None
    assert eng.scheduler.cache is eng.cache
