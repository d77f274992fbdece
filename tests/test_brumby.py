"""brumby (power retention) at a small size on the CPU: hidden 64, 4 query
heads over 2 K/V heads of 16, 3 layers, seeded weights, against the plain
reference `benchmark/lib/reference_brumby.py` (the attention form): the
dense forward, prefill and decode through `LLMEngine` with logits
compared, the three forms of the layer against each other, the feature
map's identity, a model with no K/V group admitted and freed by slots, the
state saved and restored bit for bit, what the engine refuses, and both
kernels in interpret mode.  Nothing here is a measurement.

Two sets of weights: "near_one" puts every gate in 0.9-0.999 (a constant
coordinate in the embedding against a large row of the gate's projection:
the gate has no bias), so that a sequence's first tokens still weigh at
its end and a state that is dropped, not carried or not decayed shows;
"forgetful" leaves the gates where N(0, 0.08) weights put them (near 0.5).
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
from benchmark.lib import reference_brumby as ref  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.models import (BrumbyForCausalLM, StateSpec,  # noqa: E402
                               brumby_test_config)
from paddle_tpu.ops import pallas_ops as po  # noqa: E402
from paddle_tpu.ops import power_retention as pr  # noqa: E402
from paddle_tpu.serving import EngineConfig, LLMEngine  # noqa: E402
from paddle_tpu.serving.kv_cache import CacheGroups, StateCache  # noqa: E402
from paddle_tpu.serving.scheduler import SamplingParams  # noqa: E402

CHUNK = 64      # the XLA form's chunk in `retention_prefill` off the TPU
# float32 on both sides, logits of standard deviation 0.6-0.7: the chunked
# and the recurrent form sum in another order than the reference's
# attention form, and a weight of a key far back is a product of many
# gates (5e-4 at most here); a state dropped, not decayed or read from
# the wrong slot moves a logit by tenths
LOGIT_ATOL = 2e-3


def _seeded(cfg, gates, seed=0):
    model = BrumbyForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for n, p in model.named_parameters():
        if "norm" in n:
            val = 1 + 0.1 * rng.standard_normal(p.shape)
        elif n == "embed":
            val = rng.standard_normal(p.shape)
            if gates == "near_one":
                val[:, 0] = 2.0
        else:
            val = 0.08 * rng.standard_normal(p.shape)
            if gates == "near_one" and n.startswith("g_w"):
                val[0] = 2.3
        p._data = jnp.asarray(val, p._data.dtype)
    return model


@pytest.fixture(scope="module", params=["near_one", "forgetful"])
def tiny(request):
    cfg = brumby_test_config()
    return _seeded(cfg, request.param), cfg, request.param


@pytest.fixture(scope="module")
def near_one():
    cfg = brumby_test_config()
    return _seeded(cfg, "near_one"), cfg


def _cfg_dict(cfg):
    return dataclasses.asdict(cfg)


def _prompts(cfg, lens, seed=7):
    rng = np.random.default_rng(seed)
    return [[int(t) for t in rng.integers(0, cfg.vocab_size, n)]
            for n in lens]


def _engine(model, **kw):
    base = dict(block_size=16, max_num_seqs=4, max_model_len=256)
    base.update(kw)
    return LLMEngine(model, EngineConfig(**base))


def _reference_logits(model, cfg, seq):
    return np.asarray(ref.logits(ref.params_from_model(model),
                                 jnp.asarray(seq), _cfg_dict(cfg)))


def _served_logits(eng, prompts, new_tokens):
    """Generate, and keep the float32 logits every sampled token was taken
    from: {request: [logits of its 1st, 2nd, .. token]}."""
    seen = {}
    inner = eng._dispatch_sampler

    def spy(rows, logits):
        host = np.asarray(logits)
        for i, r in enumerate(rows):
            seen.setdefault(r.req_id, []).append(host[i])
        return inner(rows, logits)

    eng._dispatch_sampler = spy
    outs = eng.generate(prompts, SamplingParams(max_new_tokens=new_tokens))
    return outs, [np.stack(seen[i]) for i in sorted(seen)]


# -- (i) the dense forward against the reference ------------------------------

def test_forward_matches_reference_logits(tiny):
    """Whole sequences through the chunked form from a zero state, every
    logit.  The near-one weights' gates do lie in 0.9-0.999."""
    model, cfg, gates = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 150))
    got = np.asarray(model(jnp.asarray(ids))._data)
    params = ref.params_from_model(model)
    for row, g in zip(ids, got):
        want = np.asarray(ref.logits(params, jnp.asarray(row),
                                     _cfg_dict(cfg)))
        assert want.std() > 0.3
        np.testing.assert_allclose(g, want, atol=LOGIT_ATOL, rtol=0)
    if gates == "near_one":
        x = ref._f32(params["embed"][ids[0]])
        p = {n: params[n][0] for n in ref._LAYER}
        log_g = np.asarray(ref._qkvg(
            x, p, hq=4, hkv=2, d=16, eps=cfg.rms_norm_eps,
            theta=cfg.rope_theta, fault=None)[3])
        lo, hi = np.quantile(np.exp(log_g), [0.05, 0.95])
        assert 0.9 <= lo and hi <= 0.999, (lo, hi)
        # so the first token still weighs at the end of 150
        assert np.exp(log_g[:, 0].sum()) > 1e-3


# -- (ii) prefill, then decode, through the engine ----------------------------

@pytest.mark.parametrize("lens,budget", [
    ((CHUNK - 1, CHUNK), None), ((CHUNK + 1, 3 * CHUNK + 5), None),
    ((1, 2, 9), None), ((130,), 48), ((70,), 1 + CHUNK)],
    ids=["under-and-at-a-chunk", "over-a-chunk-and-three", "short",
         "continuation-of-48", "continuation-over-a-chunk"])
def test_engine_serves_the_reference_logits(tiny, lens, budget):
    """Every sampled token's logits against the reference's full forward
    over prompt + served tokens: the prompt through `prefill(P)` (or, under
    a token budget, through chunked-prefill continuations that carry the
    slot's state in), the tokens through the one-position decode update, a
    mixed batch with padding rows."""
    model, cfg, _ = tiny
    prompts = _prompts(cfg, lens)
    eng = _engine(model, max_num_batched_tokens=budget)
    assert eng.caches == {} and eng.cache is None
    assert list(eng.states) == ["retention"]
    outs, logits = _served_logits(eng, prompts, 10)
    for p, o, got in zip(prompts, outs, logits):
        assert len(o) == len(p) + 10 and got.shape[0] == 10
        want = _reference_logits(model, cfg, o)[len(p) - 1:-1]
        np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    assert eng.states["retention"].slots_in_use == 0


def test_a_dropped_state_shows_in_the_logits(near_one):
    """What LOGIT_ATOL is set against: the same tokens under the
    reference's faults move a logit by twenty times as much or more
    (`no_gate` least: these gates are near 1 already)."""
    model, cfg = near_one
    seq = _prompts(cfg, (150,))[0]
    params = ref.params_from_model(model)
    sound = _reference_logits(model, cfg, seq)
    for fault in ("no_gate", "degree_1", "unnormalised"):
        moved = np.asarray(ref.logits(params, jnp.asarray(seq),
                                      _cfg_dict(cfg), fault=fault))
        assert np.abs(moved - sound).max() > 20 * LOGIT_ATOL, fault


# -- (iii) one function, three forms ------------------------------------------

def _qkvg(b, t, hq, hkv, d, seed, lo=0.9, hi=0.999):
    rng = np.random.default_rng(seed)
    q, k, v = (jnp.asarray(rng.standard_normal((b, t, h, d)), jnp.float32)
               for h in (hq, hkv, hkv))
    log_g = jnp.asarray(np.log(rng.uniform(lo, hi, (b, t, hkv))),
                        jnp.float32)
    return q, k, v, log_g


@pytest.mark.parametrize("t", [1, 7, 8, 3 * 8 + 5],
                         ids=["one", "chunk-1", "chunk", "3-chunks+5"])
def test_recurrent_attention_and_chunked_forms_agree(t):
    """Outputs of all three, and the state the recurrence and the chunked
    form end in.  2e-3 relative to the outputs' scale: at T = 1 the one
    weight is `(q . k)^2` in one form and `phi(q) . phi(k)` in the other,
    and it can be small against its terms."""
    q, k, v, log_g = _qkvg(2, t, 4, 2, 16, seed=t)
    want = np.asarray(pr.retention_attention(q, k, v, log_g))
    rec, s_rec = pr.retention_recurrent(q, k, v, log_g)
    chk, s_chk = pr.retention_chunked(q, k, v, log_g, chunk=8)
    scale = np.abs(want).max()
    np.testing.assert_allclose(rec, want, atol=2e-3 * scale, rtol=0)
    np.testing.assert_allclose(chk, want, atol=2e-3 * scale, rtol=0)
    np.testing.assert_allclose(s_chk, s_rec, rtol=0,
                               atol=1e-5 * np.abs(s_rec).max())
    assert s_rec.shape == (2,) + pr.state_shape(2, 16)
    # rows past `z` stay zero: the pool's padding
    assert not np.asarray(s_rec)[:, :, :, 17:].any()


def test_the_chunked_form_carries_a_state_in():
    q, k, v, log_g = _qkvg(1, 40, 4, 2, 16, seed=3)
    whole, s_whole = pr.retention_chunked(q, k, v, log_g, chunk=8)
    cut = 13
    _, s0 = pr.retention_chunked(q[:, :cut], k[:, :cut], v[:, :cut],
                                 log_g[:, :cut], chunk=8)
    rest, s1 = pr.retention_chunked(q[:, cut:], k[:, cut:], v[:, cut:],
                                    log_g[:, cut:], s0, chunk=8)
    np.testing.assert_allclose(rest, whole[:, cut:], atol=1e-5)
    np.testing.assert_allclose(s1, s_whole, rtol=0,
                               atol=1e-5 * np.abs(s_whole).max())


# -- (iv) the feature map -----------------------------------------------------

@pytest.mark.parametrize("d", [2, 16, 128])
def test_phi_is_the_symmetric_second_power(d):
    """`phi(u) . phi(w) == (u . w)^2`, over as many lanes as the published
    map has numbers plus the d / 2 pairs this layout keeps twice."""
    rng = np.random.default_rng(d)
    u, w = (jnp.asarray(rng.standard_normal((5, d)), jnp.float32)
            for _ in range(2))
    got = (pr.phi(u) * pr.phi(w)).sum((-2, -1))
    # float32 over up to 8,320 terms of either sign: 1e-5 of their scale
    np.testing.assert_allclose(
        got, (u * w).sum(-1) ** 2, rtol=0,
        atol=1e-5 * float(((u * u).sum(-1) * (w * w).sum(-1)).max()))
    assert pr.phi(u).shape[-2] * d == d * (d + 1) // 2 + d // 2
    assert pr.published_state_numbers(8, 128) == 8 * 8256 * 129


# -- (v) a model with no K/V group: admission by slots ------------------------

def test_an_all_state_form_is_admitted_and_freed_by_slots(near_one):
    """24 slots: 24 requests run, the 25th waits with no block to blame,
    and takes the slot of the first that ends."""
    model, cfg = near_one
    eng = _engine(model, max_num_seqs=24)
    assert isinstance(eng.kv, CacheGroups) and eng.kv.block_size is None
    assert all(isinstance(s, StateSpec) and s.in_place
               for s in eng.form.layer_specs)
    pool = eng.states["retention"]
    assert pool.state[0].shape == (25,) + pr.state_shape(2, 16)
    assert pool.state[0].dtype == jnp.float32
    prompt = _prompts(cfg, (5,))[0]
    rids = [eng.add_request(prompt, SamplingParams(
        max_new_tokens=3 if i == 0 else 8)) for i in range(25)]
    late = eng._requests[rids[-1]]
    while not eng._requests[rids[0]].finished:
        eng.step()
        assert pool.slots_in_use <= 24
        if pool.slots_in_use == 24 and not eng._requests[rids[0]].finished:
            assert late.state == late.WAITING
    while eng.has_unfinished():
        eng.step()
    assert eng.scheduler.num_evictions == 0
    outs = [eng.request_output(r) for r in rids]
    assert all(len(o) == 5 + (3 if i == 0 else 8)
               for i, o in enumerate(outs))
    # one prompt, greedy: every request decoded the same tokens
    assert all(list(o[:8]) == list(outs[1][:8]) for o in outs[1:])
    for r in rids:
        eng.release_request(r)
    assert pool.slots_in_use == 0


def test_v1_completions_serves_it(near_one):
    """Through the HTTP front door: `/v1/completions` over an engine with
    no K/V group answers with the tokens `generate()` decodes."""
    import json
    import urllib.request

    from paddle_tpu.serving.api import start_api_server

    model, cfg = near_one
    prompt = _prompts(cfg, (33,), seed=4)[0]
    want, = _engine(model).generate([prompt],
                                    SamplingParams(max_new_tokens=5))
    server = start_api_server(engine=_engine(model), port=0)
    try:
        req = urllib.request.Request(
            server.url + "/v1/completions",
            json.dumps({"prompt": prompt, "max_tokens": 5}).encode(),
            {"Content-Type": "application/json"})
        doc = json.loads(urllib.request.urlopen(req, timeout=120).read())
    finally:
        server.stop()
    assert doc["choices"][0]["token_ids"] == [int(t) for t in want[-5:]]


# -- (vi) the state saved and restored ----------------------------------------

def test_swap_out_and_in_restore_the_state_bit_for_bit(near_one):
    """A sequence's state off the device and back, into whatever slot is
    free, after another sequence has used its old one."""
    model, cfg = near_one
    eng = _engine(model, max_num_seqs=2)
    a = eng.add_request(_prompts(cfg, (20,))[0],
                        SamplingParams(max_new_tokens=4))
    eng.step()
    eng.settle()
    pool = eng.states["retention"]
    old = pool.slot_of(a)
    before = [np.asarray(s[old]) for s in pool.state]
    assert all(np.abs(x).max() > 0 for x in before)
    saved = eng.kv.swap_out(a)
    assert pool.slots_in_use == 0 and eng.kv.swap_blocks(saved) == 0
    # another sequence takes that slot and leaves it dirty
    eng.kv.allocate("other", 1)
    assert pool.slot_of("other") == old
    pool.state = [s.at[old].set(1.0) for s in pool.state]
    assert eng.kv.can_swap_in(saved)
    eng.kv.swap_in(a, saved)
    assert pool.slot_of(a) != old
    for x, s in zip(before, pool.state):
        np.testing.assert_array_equal(x, np.asarray(s[pool.slot_of(a)]))


def test_export_and_adopt_decode_on_from_the_state(near_one):
    """A request exported mid-decode ships its state and decodes on in
    another engine to the tokens of an engine it never left."""
    model, cfg = near_one
    prompt = _prompts(cfg, (70,), seed=9)[0]
    sp = SamplingParams(max_new_tokens=9)
    want, = _engine(model).generate([prompt], sp)
    src, dst = _engine(model), _engine(model)
    rid = src.add_request(prompt, sp)
    for _ in range(4):
        src.step()
    handoff = src.export_request(rid)
    moved = dst.adopt_request(handoff["prompt_ids"], handoff["params"],
                              handoff["output_ids"], handoff["key"],
                              handoff["kv"])
    while dst.has_unfinished():
        dst.step()
    np.testing.assert_array_equal(dst.request_output(moved), want)
    assert src.states["retention"].slots_in_use == 0


# -- (vii) what the engine refuses --------------------------------------------

@pytest.mark.parametrize("option", [
    {"kv_cache_dtype": "int8"}, {"speculative_tokens": 2},
    {"enable_prefix_caching": True}], ids=lambda o: next(iter(o)))
def test_options_that_need_kv_blocks_raise_by_name(near_one, option):
    model, _ = near_one
    with pytest.raises(ValueError, match=next(iter(option))):
        LLMEngine(model, EngineConfig(block_size=16, max_model_len=32,
                                      **option))


# -- (viii) the kernels, in interpret mode ------------------------------------

@pytest.fixture
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()


def _pool(slots, hkv, d, seed):
    """A pool whose every slot holds a state a prefill could have left."""
    pool = []
    for i in range(slots + 1):
        q, k, v, log_g = _qkvg(1, 12, hkv, hkv, d, seed=seed + i)
        pool.append(pr.retention_chunked(q, k, v, log_g, chunk=12)[1][0])
    return jnp.stack(pool)


def test_decode_kernel_equals_the_xla_form(_interpret_mode):
    """Three rows over two K/V heads of 128 with two query heads each, one
    row a padding row on the dropped slot: outputs and the states written
    (float32 sums in another order: 1e-5 of the state's scale), the slot
    nobody names untouched, the padding row's output 0."""
    pool = _pool(3, 2, 128, seed=0).at[3].set(0.0)   # the dropped slot
    q, k, v, log_g = (a[:, 0] for a in _qkvg(3, 1, 4, 2, 128, seed=5))
    slots = jnp.asarray([2, 0, 3], jnp.int32)
    valid = jnp.asarray([True, True, False])
    o, new = pr.retention_decode(q, k, v, log_g, pool, slots, valid,
                                 fast=False)
    assert po.attention_path_counts().get("retention_decode_kernel") == 1
    want_o, want_s = pr._decode_math(q, k, v, log_g, pool[slots])
    np.testing.assert_allclose(o[:2], want_o[:2], rtol=0,
                               atol=1e-4 * np.abs(want_o[:2]).max())
    np.testing.assert_allclose(new[slots[:2]], want_s[:2], rtol=0,
                               atol=1e-5 * np.abs(want_s).max())
    np.testing.assert_array_equal(new[1], pool[1])
    assert not np.asarray(o[2]).any() and not np.asarray(new[3]).any()


@pytest.mark.parametrize("t,fresh", [(128, True), (300, False)],
                         ids=["fresh-one-chunk", "carried-three-chunks"])
def test_prefill_kernel_equals_the_xla_form(_interpret_mode, t, fresh):
    """float32 operands at the highest precision on both sides (`fast`
    off): what differs is the order of the sums."""
    pool = _pool(2, 2, 128, seed=1)
    q, k, v, log_g = _qkvg(1, t, 4, 2, 128, seed=t)
    slot = jnp.asarray([1], jnp.int32)
    o, new = pr.retention_prefill(q, k, v, log_g, pool, slot, fresh,
                                  chunk=128, fast=False)
    assert po.attention_path_counts().get("retention_prefill_kernel") == 1
    want_o, want_s = pr.retention_chunked(
        q, k, v, log_g, None if fresh else pool[slot], chunk=64)
    np.testing.assert_allclose(o, want_o, rtol=0,
                               atol=1e-4 * np.abs(want_o).max())
    np.testing.assert_allclose(new[slot], want_s, rtol=0,
                               atol=1e-5 * np.abs(want_s).max())
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[2], pool[2])


def test_prefill_kernel_in_bfloat16_stays_near(_interpret_mode):
    """The chip's setting: the MXU's operands in bfloat16, sums in
    float32.  2% of the outputs' scale: 2^-8 a product, averaged."""
    pool = _pool(1, 1, 128, seed=2)
    q, k, v, log_g = (a.astype(jnp.bfloat16) if i < 3 else a for i, a in
                      enumerate(_qkvg(1, 256, 2, 1, 128, seed=11)))
    slot = jnp.asarray([0], jnp.int32)
    o, new = pr.retention_prefill(q, k, v, log_g, pool, slot, True,
                                  chunk=128)
    want_o, want_s = pr.retention_chunked(q, k, v, log_g, chunk=64)
    np.testing.assert_allclose(o.astype(jnp.float32), want_o, rtol=0,
                               atol=2e-2 * np.abs(want_o).max())
    np.testing.assert_allclose(new[slot], want_s, rtol=0,
                               atol=2e-2 * np.abs(want_s).max())


def test_engine_takes_both_kernels(_interpret_mode):
    """Through the engine in interpret mode at heads of 128: the prompt
    counts the prefill kernel, decode the decode kernel, nothing falls
    back, and the tokens' logits are the reference's (bfloat16 products
    in the prefill kernel: 5e-2 on logits of 0.6)."""
    cfg = brumby_test_config(num_attention_heads=2, num_key_value_heads=1,
                             head_dim=128, num_hidden_layers=2,
                             max_position_embeddings=512)
    model = _seeded(cfg, "near_one", seed=4)
    prompts = _prompts(cfg, (130,), seed=2)
    eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2,
                                        max_model_len=256))
    outs, logits = _served_logits(eng, prompts, 3)
    counts = po.attention_path_counts()
    assert counts.get("retention_prefill_kernel") == 2
    assert counts.get("retention_decode_kernel") == 2
    assert not [k for k in counts if "_fallback:" in k], counts
    want = _reference_logits(model, cfg, outs[0])[len(prompts[0]) - 1:-1]
    np.testing.assert_allclose(logits[0], want, atol=5e-2, rtol=0)


# -- spans and counters -------------------------------------------------------

def test_retention_counters_and_scopes(near_one):
    model, cfg = near_one
    eng = _engine(model)

    def val(name, **labels):
        return monitor.snapshot().get(name, {}).get(
            ",".join(f"{k}={v}" for k, v in sorted(labels.items())), 0)

    pool = eng.states["retention"]
    assert val("serving/state_bytes", group="retention") == pool.pool_bytes \
        == 3 * 5 * int(np.prod(pr.state_shape(2, 16))) * 4
    before = {ph: val("serving/retention_tokens", phase=ph)
              for ph in ("prefill", "decode")}
    steps0 = val("serving/state_slot_steps", group="retention")
    rid = eng.add_request(_prompts(cfg, (7,))[0],
                          SamplingParams(max_new_tokens=4))
    eng.step()
    assert val("serving/state_slots_in_use", group="retention") == 1
    while eng.has_unfinished():
        eng.step()
    eng.release_request(rid)
    # a prompt of 7 and 3 decode steps of one row, through 3 layers
    assert val("serving/retention_tokens", phase="prefill") \
        - before["prefill"] == 7 * 3
    assert val("serving/retention_tokens", phase="decode") \
        - before["decode"] == 3 * 3
    assert val("serving/state_slot_steps", group="retention") - steps0 == 3
    assert val("serving/state_slots_in_use", group="retention") == 0
    toks, pos0, lens, tables, slots, srows = eng._decode_inputs([], [], 4, 1)
    assert tables == () and slots == () and len(srows) == 1
    text = eng._get_ragged_exec(4, 1).lower(
        eng._param_arrays(), eng._kv_flat(), toks, pos0, lens, tables,
        slots, srows).as_text(debug_info=True)
    assert "retention/decode" in text
    text = eng._get_prefill_exec(7).lower(
        eng._param_arrays(), eng._kv_flat(), np.zeros((1, 7), np.int32),
        (), (np.zeros((1,), np.int32),)).as_text(debug_info=True)
    assert "retention/prefill" in text


def test_state_cache_counts_in_slots():
    pool = StateCache(2, 3, pr.state_shape(1, 2), jnp.float32, name="r")
    groups = CacheGroups({"r": pool})
    assert groups.num_blocks == 3 and groups.blocks_needed(1000) == 1
    groups.allocate(7, 50)
    assert groups.num_free_blocks == 2 and 7 in groups._tables
    groups.grow_to(7, 5000)
    assert groups.can_grow_to(7, 10 ** 6)
    groups.free(7)
    assert groups.num_free_blocks == 3
