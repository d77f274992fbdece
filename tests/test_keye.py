"""keye through `LLMEngine` at a small size on the CPU (hidden 64, 4 query
heads over 2 K/V heads of 16, 4 index heads of 16 keeping 8 keys, 3
layers, 8 experts top-2 of which 4 held here, prompts up to 48 so that
the selection is sparse), seeded weights, against the plain reference
`benchmark/lib/reference_keye.py`; the selection rule and the kernels of
`ops/sparse_attention.py` (in interpret mode); the index pool through the
allocator; the expert layer's eight shares under softmax routing; and what
the engine refuses.  Nothing here is a measurement.
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
from benchmark.lib import reference_keye as ref  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.models import (KeyeForCausalLM, SparseSpec,  # noqa: E402
                               keye_test_config)
from paddle_tpu.ops import pallas_ops as po  # noqa: E402
from paddle_tpu.ops import sparse_attention as sa  # noqa: E402
from paddle_tpu.parallel.moe import held_experts_arrays  # noqa: E402
from paddle_tpu.serving import EngineConfig, LLMEngine  # noqa: E402
from paddle_tpu.serving.kv_cache import BlockKVCache  # noqa: E402
from paddle_tpu.serving.scheduler import SamplingParams  # noqa: E402

BS = 4
# float32 on both sides: the program and the reference sum the indexer's
# heads, the softmax and the experts in other orders (measured 2e-6 of a
# logit); a wrong key, index key, selection, norm or expert moves a logit
# by hundredths, and bfloat16 in place of float32 by more than 3e-3
LOGIT_TOL = 1e-4


def _seeded(cfg, seed=0):
    """A model of `cfg` with weights from `seed`: matrices N(0, 0.08),
    norm scales near 1 and the index key's LayerNorm bias near 0 (not
    exactly: a forgotten norm or bias must show)."""
    model = KeyeForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for n, p in model.named_parameters():
        if "norm" in n:
            val = 1 + 0.1 * rng.standard_normal(p.shape)
        elif "bias" in n:
            val = 0.1 * rng.standard_normal(p.shape)
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
    cfg = keye_test_config()
    return _seeded(cfg), cfg


def _ref_logits(model, cfg, seq, **kw):
    return np.asarray(ref.logits(ref.params_from_model(model),
                                 jnp.asarray(seq), _cfg_dict(cfg), **kw))


def _engine(model, **kw):
    base = dict(block_size=BS, max_num_seqs=4, max_model_len=96)
    base.update(kw)
    return LLMEngine(model, EngineConfig(**base))


def _prompts(cfg, lens, seed=7):
    rng = np.random.default_rng(seed)
    return [list(rng.integers(0, cfg.vocab_size, n)) for n in lens]


def _served_logits(eng, prompts, sp):
    """Every logits row the engine sampled from, by request."""
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
    """Whole sequences well past `topk`, no cache: every logit."""
    model, cfg = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 45))
    got = np.asarray(model(jnp.asarray(ids))._data)
    for row, g in zip(ids, got):
        want = _ref_logits(model, cfg, row)
        assert want.std() > 0.3
        np.testing.assert_allclose(g, want, atol=LOGIT_TOL, rtol=0)


@pytest.mark.parametrize("lens,rows", [
    ((5, 13, 41), 4), ((3, 8, 9, 33), 4), ((1, 2), 4), ((48,), 2)],
    ids=["mixed", "around-topk", "shortest", "longest"])
def test_engine_logits_are_the_references(tiny, lens, rows):
    """Prefill then decode through the three pools, a mixed batch, fewer
    rows than the program's: every logits row the engine sampled from is
    the reference's row of its full forward over prompt + served tokens."""
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
    assert eng.cache.blocks_in_use == 0


def test_bfloat16_in_place_of_float32_fails_the_tolerance(tiny):
    model, cfg = tiny
    low = _seeded(cfg)
    low.to(dtype="bfloat16")
    prompt, = _prompts(cfg, (21,))
    outs, logits = _served_logits(_engine(low), [prompt],
                                  SamplingParams(max_new_tokens=6))
    want = _ref_logits(model, cfg, outs[0])[len(prompt) - 1:-1]
    assert np.abs(np.stack(logits[0]) - want).max() > 30 * LOGIT_TOL


@pytest.fixture(scope="module")
def served(tiny):
    """Tokens the engine served over contexts past `topk` and the logits
    rows it sampled them from, by request."""
    model, cfg = tiny
    prompts = _prompts(cfg, (30, 44), seed=13)
    outs, logits = _served_logits(_engine(model), prompts,
                                  SamplingParams(max_new_tokens=16))
    return [(o, len(p), np.stack(lg))
            for o, p, lg in zip(outs, prompts, logits)]


@pytest.mark.parametrize("fault", [f for f in ref.FAULTS if f])
def test_each_fault_of_the_reference_fails_the_comparison(tiny, served,
                                                          fault):
    """The served logits pass the sound reference at LOGIT_TOL and fail the
    reference computed wrongly on purpose by ten times it: a lower
    precision, every key attended, the last topk keys whatever the indexer
    says, index keys never stored."""
    model, cfg = tiny

    def worst(**kw):
        return max(np.abs(lg - _ref_logits(model, cfg, o, **kw)[n - 1:-1]
                          ).max() for o, n, lg in served)

    assert worst() <= LOGIT_TOL < 10 * LOGIT_TOL < worst(fault=fault)


def test_reference_runs_a_padded_row_up_to_its_tokens(tiny, monkeypatch):
    """`greedy_margins` runs a row padded with zeros only to K_CHUNK past
    its last token: the margins there are the whole row's, 0 past it."""
    model, cfg = tiny
    params = ref.params_from_model(model)
    row = np.zeros((1, 128), np.int32)
    row[0, :40] = np.random.default_rng(3).integers(1, cfg.vocab_size, 40)
    whole, sd = ref.greedy_margins(params, row, _cfg_dict(cfg))
    monkeypatch.setattr(ref, "K_CHUNK", 16)
    assert ref._used(row[0]) == 64 and ref._used(np.zeros(8)) == 8
    cut, sd_cut = ref.greedy_margins(params, row, _cfg_dict(cfg))
    np.testing.assert_allclose(cut[0, :63], whole[0, :63], atol=1e-5)
    np.testing.assert_allclose(sd_cut[0, :63], sd[0, :63], atol=1e-5)
    assert not cut[0, 63:].any()


def test_index_keys_not_stored_fail_against_the_reference(tiny, served,
                                                          monkeypatch):
    """The program's own fault: the chunk's index keys are not written to
    the pool, so every decode step scores its context against zeros."""
    model, cfg = tiny
    from paddle_tpu.serving import engine as engine_mod

    real = engine_mod.paged_cache_update_arrays

    def skip_index(blocks, rows, slots):
        if blocks.shape[-1] == sa.index_pool_lanes(cfg.index_dim) \
                and rows.shape[-1] == blocks.shape[-1] \
                and slots.shape[1] > 1:
            return blocks                     # the prefill's chunk
        return real(blocks, rows, slots)

    monkeypatch.setattr(engine_mod, "paged_cache_update_arrays", skip_index)
    o, n, _ = served[1]
    outs, logits = _served_logits(_engine(model), [o[:n]],
                                  SamplingParams(max_new_tokens=16))
    want = _ref_logits(model, cfg, outs[0])[n - 1:-1]
    assert np.abs(np.stack(logits[0]) - want).max() > 10 * LOGIT_TOL


def test_at_contexts_within_topk_the_layer_is_dense_gqa(tiny):
    """Up to `topk` positions every key is selected: the forward equals
    the same layers over dense causal GQA."""
    model, cfg = tiny
    form, params = model.serving_form(), model.param_arrays()
    ids = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (1, cfg.topk)))

    def dense(q, k, v, qi, ki, w):
        return po.mha_reference(q, k, v, is_causal=True,
                                scale=cfg.head_dim ** -0.5), None

    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
    h = form.embed(params, ids, pos)
    for l in range(cfg.num_hidden_layers):
        h, _, _ = form.layer(l, params, h, pos, dense)
    np.testing.assert_allclose(
        np.asarray(form.logits(params, h)),
        np.asarray(model.forward_arrays(params, ids)), atol=2e-5, rtol=0)


def test_a_long_prompt_is_projected_a_block_of_rows_at_a_time(
        tiny, monkeypatch):
    """Past `_ROWS` positions a prompt's projections and feed-forward run
    a block of rows at a time (their temporaries a block's): the layer's
    output and its counts are those of the whole, but the experts touched,
    which a block counts of its own rows (a prefill's, summed)."""
    from paddle_tpu.models import keye

    model, cfg = tiny
    form, params = model.serving_form(), model.param_arrays()
    ids = jnp.asarray(np.random.default_rng(8).integers(
        0, cfg.vocab_size, (1, 48)))
    pos = jnp.arange(48, dtype=jnp.int32)

    def run():
        h = form.embed(params, ids, pos)
        h, _, stats = form.layer(
            0, params, h, pos, lambda *a: (sa.sparse_prefill_attention(
                *a, cfg.topk, cfg.head_dim ** -0.5), None))
        return np.asarray(h), np.asarray(stats)

    whole = run()
    monkeypatch.setattr(keye, "_ROWS", 16)
    blocks = run()
    np.testing.assert_allclose(blocks[0], whole[0], atol=1e-5)
    touched = 2               # `held_experts_arrays`' third count
    np.testing.assert_array_equal(np.delete(blocks[1], touched),
                                  np.delete(whole[1], touched))
    assert blocks[1][touched] >= whole[1][touched]


# -- (b) the selection rule ----------------------------------------------------

def _top_k_sets(scores, lens, k):
    out = np.zeros(scores.shape, bool)
    for r, n in enumerate(lens):
        if n:
            _, at = jax.lax.top_k(jnp.asarray(scores[r, :n]), min(k, int(n)))
            out[r, np.asarray(at)] = True
    return out


def _tied_scores(rng, rows, width):
    s = rng.standard_normal((rows, width)).astype(np.float32)
    s[0] = 0.0                              # every score ties
    s[1, 5:500] = 9.0                       # ties that overflow the top
    s[2, ::3] = 0.75                        # ties at the boundary
    s[3] = -0.0
    s[3, 7] = 0.0                           # -0.0 is +0.0
    return s


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def test_selection_is_lax_top_k(kernel, monkeypatch):
    """The bisection over the scores' bits keeps exactly the positions
    `lax.top_k` returns, ties to the lower position: rows of ties, ties
    past the topk, a row shorter than topk and an empty one."""
    rng = np.random.default_rng(0)
    s = _tied_scores(rng, 8, 2048)
    lens = np.array([2048, 2048, 1000, 2048, 70, 64, 63, 0], np.int32)
    keys = sa.sort_keys(jnp.asarray(s))
    if kernel:
        monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")
        sel = sa._select_call(jnp.asarray(s), jnp.asarray(lens), 64, True)
        theta, last = sel[:, 0:1], sel[:, 1:2]
    else:
        theta, last = sa.select_threshold(keys, jnp.asarray(lens), 64)
    pos = jnp.arange(2048)[None]
    got = np.asarray(sa.selected(keys, pos, pos < lens[:, None], theta,
                                 last))
    want = _top_k_sets(np.where(np.arange(2048) < lens[:, None], s, -np.inf),
                       lens, 64)
    np.testing.assert_array_equal(got, want)
    assert (got.sum(1) == np.minimum(lens, 64)).all()


def test_reference_kth_is_lax_top_k():
    """The reference's bisection finds the value and the position of the
    k-th of `lax.top_k`: ties everywhere, ties past k, rows of -inf
    (positions after a query), k as wide as the row; -0.0 ties with +0.0
    (which `lax.top_k` orders above it)."""
    rng = np.random.default_rng(4)
    every = _tied_scores(rng, 6, 600)
    every[3] = 0.5
    every[4, 100:] = -np.inf
    every = jnp.asarray(every)
    for k in (8, 64, 600):
        got = ref._kth(every, k)
        top, at = jax.lax.top_k(every, k)
        np.testing.assert_array_equal(np.asarray(got[0]),
                                      np.asarray(top[:, -1:]))
        np.testing.assert_array_equal(np.asarray(got[1]),
                                      np.asarray(at[:, -1:]))
    zeros = jnp.asarray([[-0.0] * 7 + [0.0] + [-0.0] * 8])
    assert int(ref._kth(zeros, 8)[1][0, 0]) == 7


def test_program_selection_is_the_references(tiny):
    """At float32 the program's selection over a layer's real scores is
    the reference's `lax.top_k`, query by query."""
    model, cfg = tiny
    params = model.param_arrays()
    rng = np.random.default_rng(9)
    t = 40
    qi = jnp.asarray(rng.standard_normal((t, cfg.index_heads,
                                          cfg.index_dim)), jnp.float32)
    ki = jnp.asarray(rng.standard_normal((t, cfg.index_dim)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((t, cfg.index_heads)), jnp.float32)
    scores = sa.index_scores(qi, w, ki[None])
    pos = jnp.arange(t)
    valid = pos[None] <= pos[:, None]
    keys = sa.sort_keys(jnp.where(valid, scores, -jnp.inf))
    theta, last = sa.select_threshold(keys, pos + 1, cfg.topk)
    got = np.asarray(sa.selected(keys, pos[None], valid, theta, last))
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(ref._scores(qi, w, ki, None))
    want = _top_k_sets(np.where(np.asarray(valid), theirs, -np.inf),
                       np.arange(1, t + 1), cfg.topk)
    np.testing.assert_array_equal(got, want)
    assert params["iq_w"][0].shape == (cfg.hidden_size,
                                       cfg.index_heads * cfg.index_dim)


# -- (c) the kernels, in interpret mode ----------------------------------------

@pytest.fixture
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    yield
    po.reset_attention_path_counts()


def _normal(rng, shape, dt=jnp.bfloat16):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dt)


def _prefill_xla_by_kv_head(args, k, scale):
    """`_prefill_xla` over one K/V head and its query heads at a time (the
    heads are independent): a [G, T, T] score array at a time."""
    q, kk, vv, qi, ki, w = (x[0] for x in args)
    group = q.shape[1] // kk.shape[1]
    return jnp.concatenate([sa._prefill_xla(
        q[:, i * group:(i + 1) * group], kk[:, i:i + 1], vv[:, i:i + 1], qi,
        ki, w, k, scale) for i in range(kk.shape[1])], axis=1)[None]


@pytest.mark.parametrize("t,h,g", [(700, 4, 2), (2048 + 515, 4, 2),
                                   (2700, 32, 4)],
                         ids=["one-block", "two", "cell-heads"])
def test_prefill_kernels_equal_the_xla_form(_interpret_mode, t, h, g):
    """Scores by tiles to the diagonal, the selection, flash over streamed
    K/V masked by it: the XLA form's output to bfloat16's rounding, over
    one and several super-blocks of rows (the prompt padded to whole
    tiles), at two query heads a K/V head and at the cell's 32 over 4."""
    rng = np.random.default_rng(1)
    d, hi, di, k = 128, 4, 64, 48
    args = (_normal(rng, (1, t, h, d)), _normal(rng, (1, t, g, d)),
            _normal(rng, (1, t, g, d)), _normal(rng, (1, t, hi, di)),
            _normal(rng, (1, t, di)),
            jnp.asarray(rng.standard_normal((1, t, hi)), jnp.float32))
    got = sa.sparse_prefill_attention(*args, k, d ** -0.5)
    assert po.attention_path_counts() == {"dsa_prefill_kernel": 1}
    want = _prefill_xla_by_kv_head(args, k, d ** -0.5)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=3e-2)


def test_prefill_rows_whose_earlier_tiles_hold_no_selected_key(
        _interpret_mode):
    """Index scores that grow with the key's position (index key s reads
    256 * (s // 256) + s % 256 against the one live index head), so each
    row selects its last topk positions and a row past the topk-th of
    its key tile selects keys of that tile alone: the flash sums every
    earlier tile under m = _NEG, and the first selected key must clear
    it.  The XLA form's output, and each such row is its last topk
    keys' attention."""
    rng = np.random.default_rng(3)
    t, h, g, d, hi, di, k = 1100, 16, 2, 128, 4, 64, 48
    pos = np.arange(t)
    ki = np.zeros((1, t, di), np.float32)
    ki[0, :, 0], ki[0, :, 1] = pos // 256, pos % 256
    qi = np.zeros((1, t, hi, di), np.float32)
    qi[0, :, 0, 0], qi[0, :, 0, 1] = 256.0, 1.0
    w = np.zeros((1, t, hi), np.float32)
    w[0, :, 0] = 1.0
    q, kk, vv = (_normal(rng, (1, t, n, d)) for n in (h, g, g))
    args = (q, kk, vv, jnp.asarray(qi, jnp.bfloat16),
            jnp.asarray(ki, jnp.bfloat16), jnp.asarray(w))
    got = np.asarray(sa.sparse_prefill_attention(*args, k, d ** -0.5),
                     np.float32)
    want = sa._prefill_xla(*(x[0] for x in args), k, d ** -0.5)[None]
    np.testing.assert_allclose(got, np.asarray(want, np.float32), atol=3e-2)
    late = [r for r in range(t) if r % sa._TK >= k - 1]
    assert len(late) > t // 2
    kf, vf = (np.asarray(x[0], np.float32) for x in (kk, vv))
    for r in late[::97]:
        last = slice(r - k + 1, r + 1)
        for head in range(h):
            kv = head // (h // g)
            s = np.asarray(q[0, r, head], np.float32) @ kf[last, kv].T
            p = np.exp((s - s.max()) * d ** -0.5)
            o = (p / p.sum()) @ vf[last, kv]
            np.testing.assert_allclose(got[0, r, head], o, atol=3e-2)


def _decode_case(rng, lens, bs=64, nb=64, maxb=18, h=4, g=2, d=128, hi=4,
                 di=64):
    kp, vp = _normal(rng, (nb, bs, g * d)), _normal(rng, (nb, bs, g * d))
    ip = jnp.pad(_normal(rng, (nb, bs, di)), ((0, 0), (0, 0), (0, 64)))
    tables = np.full((len(lens), maxb), nb, np.int32)
    perm, at = rng.permutation(nb), 0
    for i, n in enumerate(lens):
        m = -(-n // bs)
        tables[i, :m] = perm[at:at + m]
        at += m
    return (_normal(rng, (len(lens), h, d)), _normal(rng, (len(lens), hi, di)),
            jnp.asarray(rng.standard_normal((len(lens), hi)), jnp.float32),
            kp, vp, ip, jnp.asarray(tables), jnp.asarray(lens, jnp.int32))


def test_decode_kernels_equal_the_xla_form(_interpret_mode):
    """The index stream through the block table (rows of 0-1 tokens, one
    that ends on a tile's edge, one past it, one filling its table), the
    selection kernel, the gather: the XLA form's output; and a row against
    a brute force over its context."""
    rng = np.random.default_rng(2)
    lens = [700, 30, 0, 1, 1024, 1025, 768]
    args = _decode_case(rng, lens)
    got = sa.sparse_decode_attention(*args, 48, 0.088)
    assert po.attention_path_counts() == {"dsa_decode_kernel": 1}
    want = sa._decode_xla(*args, 48, 0.088)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=1e-2)
    assert not np.asarray(got, np.float32)[2].any()
    q, qi, w, kp, vp, ip, tables, n = args
    r = 0
    slots = (np.asarray(tables)[r][np.arange(lens[r]) // 64] * 64
             + np.arange(lens[r]) % 64)
    rows = (lambda p: np.asarray(p.reshape(-1, p.shape[-1])[slots],
                                 np.float32))
    kk, vv, ii = rows(kp), rows(vp), rows(ip)[:, :64]
    score = sum(np.asarray(w)[r, j] * np.maximum(
        np.asarray(qi[r, j], np.float32) @ ii.T, 0) for j in range(4))
    sel = np.asarray(jax.lax.top_k(jnp.asarray(score), 48)[1])
    for head in range(4):
        s = np.asarray(q[r, head], np.float32) @ kk[sel, (head // 2) * 128:
                                                    (head // 2 + 1) * 128].T
        p = np.exp(0.088 * (s - s.max()))
        o = (p / p.sum()) @ vv[sel, (head // 2) * 128:(head // 2 + 1) * 128]
        np.testing.assert_allclose(np.asarray(got[r, head], np.float32), o,
                                   atol=1e-2)


def test_engine_takes_the_kernels(_interpret_mode, tiny, monkeypatch):
    """With the kernels on (interpret mode), the engine's prefill and
    decode take them and serve the tokens the XLA forms - what the CPU
    takes without interpret mode - serve."""
    model, cfg = tiny
    prompts = _prompts(cfg, (33, 20))
    eng = _engine(model, block_size=64, max_model_len=128, max_num_seqs=2)
    got = eng.generate(prompts, SamplingParams(max_new_tokens=6))
    counts = po.attention_path_counts()
    assert counts.get("dsa_prefill_kernel") and counts.get("dsa_decode_kernel")
    assert not any("fallback" in key for key in counts)
    monkeypatch.delenv("PTPU_PALLAS_INTERPRET")
    po.reset_attention_path_counts()
    want = _engine(model, block_size=64, max_model_len=128,
                   max_num_seqs=2).generate(
        prompts, SamplingParams(max_new_tokens=6))
    assert set(po.attention_path_counts()) == {"dsa_fallback:off_tpu"}
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


# -- (d) the index pool, the counters, what the engine refuses -----------------

def test_a_sparse_group_keeps_three_pools_a_layer(tiny):
    model, cfg = tiny
    eng = _engine(model)
    assert list(eng.caches) == ["sparse"] and not eng.states
    cache = eng.cache
    assert cache.pool_names == ("k_blocks", "v_blocks", "i_blocks")
    assert len(eng._kv_flat()) == 3 * cfg.num_hidden_layers
    assert cache.i_blocks[0].shape == (cache.num_blocks, BS, 128)
    spec = eng.form.layer_specs[0]
    assert isinstance(spec, SparseSpec) and spec.index_lanes == 128
    # K and V of 2 heads of 16, the index key padded to a lane tile
    assert cache.bytes_per_block == cfg.num_hidden_layers * BS * (
        2 * 32 + 128) * 4
    with pytest.raises(ValueError, match="index pool"):
        BlockKVCache(1, 4, 4, 1, 128, index_lanes=128, kv_quant="int8")


def test_swap_out_and_in_restore_the_index_keys_bit_for_bit(tiny):
    model, cfg = tiny
    eng = _engine(model)
    rid = eng.add_request(_prompts(cfg, (14,))[0],
                          SamplingParams(max_new_tokens=3))
    eng.step()
    eng.settle()
    cache = eng.cache
    table = list(cache.block_table(rid))
    before = [np.asarray(p[np.asarray(table)]) for p in cache.i_blocks]
    saved = cache.swap_out(rid)
    assert "i" in saved
    cache.swap_in(rid, saved)
    after = [np.asarray(p[np.asarray(cache.block_table(rid))])
             for p in cache.i_blocks]
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert np.abs(before[0]).sum() > 0


@pytest.mark.parametrize("option", [
    {"kv_cache_dtype": "int8"}, {"speculative_tokens": 2},
    {"enable_prefix_caching": True}, {"max_num_batched_tokens": 16}],
    ids=lambda o: next(iter(o)))
def test_options_not_carried_to_the_family_raise_by_name(tiny, option):
    model, _ = tiny
    with pytest.raises(ValueError, match=next(iter(option))):
        LLMEngine(model, EngineConfig(block_size=BS, max_model_len=32,
                                      **option))


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("tie_word_embeddings", True),
    ("decoder_sparse_step", 2), ("mlp_only_layers", [0]),
    ("num_local_experts", 3)])
def test_config_refuses_what_the_family_file_does_not_build(key, value):
    with pytest.raises(ValueError, match=key):
        keye_test_config(**{key: value})


def test_sparse_counters_and_scopes(tiny):
    model, cfg = tiny
    eng = _engine(model)

    def val(name, **labels):
        return monitor.snapshot().get(name, {}).get(
            ",".join(f"{k}={v}" for k, v in sorted(labels.items())), 0)

    scored0 = {p: val("serving/dsa_keys_scored", phase=p)
               for p in ("prefill", "decode")}
    chosen0 = {p: val("serving/dsa_keys_selected", phase=p)
               for p in ("prefill", "decode")}
    live0 = val("serving/kv_tokens_live", group="sparse")
    rid = eng.add_request(_prompts(cfg, (10,))[0],
                          SamplingParams(max_new_tokens=4))
    while eng.has_unfinished():
        eng.step()
    eng.release_request(rid)
    layers = cfg.num_hidden_layers
    # prefill: positions 1..10 each score t keys and keep min(t, 8)
    assert val("serving/dsa_keys_scored", phase="prefill") \
        - scored0["prefill"] == layers * 55
    assert val("serving/dsa_keys_selected", phase="prefill") \
        - chosen0["prefill"] == layers * (36 + 8 + 8)
    # 3 decode steps at lengths 11, 12, 13: all scored, 8 kept each
    assert val("serving/dsa_keys_scored", phase="decode") \
        - scored0["decode"] == layers * 36
    assert val("serving/dsa_keys_selected", phase="decode") \
        - chosen0["decode"] == layers * 24
    assert val("serving/kv_tokens_live", group="sparse") - live0 == 36
    toks, pos0, lens, tables, slots, srows = eng._decode_inputs([], [], 4, 1)
    decode = eng._get_ragged_exec(4, 1).lower(
        eng._param_arrays(), eng._kv_flat(), toks, pos0, lens, tables,
        slots, srows).as_text(debug_info=True)
    prefill = eng._get_prefill_exec(12).lower(
        eng._param_arrays(), eng._kv_flat(), np.zeros((1, 12), np.int32),
        (np.arange(12, dtype=np.int32)[None],), ()).as_text(debug_info=True)
    for text in (decode, prefill):
        for scope in ("dsa/indexer", "dsa/select", "attn/sparse",
                      "keye/router", "keye/experts"):
            assert scope in text, scope


# -- (e) the expert layer's shares under softmax routing -----------------------

def test_eight_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 chips, 2 a chip, each share routing over all 16
    with a softmax: the eight partial results add up to what the uncut
    reference gives for the whole layer, every pair on exactly one chip;
    attention and the indexer, replicated, are computed once."""
    cfg = keye_test_config(num_experts=16, num_local_experts=16,
                           router_experts=16, num_hidden_layers=1)
    model = _seeded(cfg, seed=4)
    params = ref.params_from_model(model)
    e = {n: params[n][0] for n in ref._MOE}
    m = jnp.asarray(np.random.default_rng(3).standard_normal((24, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        sel, w, *_ = ref._route(m, e["router_w"], top_k=2, fault=None,
                                held=(0, 16))
        uncut = np.asarray(ref._experts(m, sel, w, e, range(16), 0, None))
        ref_parts = sum(
            np.asarray(ref._experts(
                m, sel, w, {k: e[k][first:first + 2] for k in (
                    "exp_gate_w", "exp_up_w", "exp_down_w")},
                range(first, first + 2), first, None))
            for first in range(0, 16, 2))
    np.testing.assert_allclose(ref_parts, uncut, atol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    parts, held = 0.0, 0
    softmax = model.serving_form().layer.__globals__["_ROUTE"]
    for first in range(0, 16, 2):
        y, stats = held_experts_arrays(
            m, e["router_w"], jnp.zeros((16,), jnp.float32),
            tuple(e[k][first:first + 2] for k in (
                "exp_gate_w", "exp_up_w", "exp_down_w")),
            first, 2, 2, 1.0, norm_eps=0.0, score=softmax)
        parts = parts + np.asarray(y)
        held += int(stats[0])
        assert int(stats[0]) + int(stats[1]) == 2 * 24
    assert held == 2 * 24
    np.testing.assert_allclose(parts, uncut, atol=1e-5, rtol=1e-5)
    # the whole layer: attention (with the indexer) once, plus the shares
    ids = jnp.asarray(np.random.default_rng(5).integers(0, 96, (20,)))
    out = []
    ref.logits(params, ids, _cfg_dict(cfg), held=range(16), layer_out=out)
    cut = []
    for first in range(0, 16, 2):
        part = []
        ref.logits(params, ids, _cfg_dict(cfg), held=range(first, first + 2),
                   layer_out=part)
        cut.append(np.asarray(part[0]))
    np.testing.assert_allclose(sum(cut), np.asarray(out[0]), atol=1e-5)


def test_route_top_k_keeps_its_sigmoid_for_the_other_families():
    """Without `score` the router is the sigmoid the afmoe, lfm2 and
    mistral4 forms route with, bit for bit."""
    from paddle_tpu.parallel.moe import route_top_k

    rng = np.random.default_rng(0)
    m = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
    rw = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    bias = jnp.asarray(rng.standard_normal((8,)), jnp.float32)
    sel, w = route_top_k(m, rw, bias, 2, 1.5, 1e-20)
    scores = jax.nn.sigmoid(m @ rw)
    _, want = jax.lax.top_k(scores + bias, 2)
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(want))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(want), -1)
    np.testing.assert_allclose(np.asarray(w),
                               picked / (picked.sum(-1, keepdims=True)
                                         + 1e-20) * 1.5, rtol=1e-6)
