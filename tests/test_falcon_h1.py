"""falcon_h1 (attention beside a Mamba-2 mixer in every layer) at a small
size on the CPU: hidden 64, 4 query heads over 2 K/V heads of 16, a mixer
of 4 heads of 16 over 2 groups of a 16-wide state in chunks of 16, 2
layers, seeded weights, against the plain reference
`benchmark/lib/reference_falcon_h1.py` (the state a position at a time):
the dense forward, prefill and decode through `LLMEngine` with logits
compared, the SSD's forms against each other, each of a layer's three
memories forked, swapped, exported and preempted bit for bit, what the
engine refuses, and both kernels in interpret mode.  Nothing here is a
measurement.

The weights are seeded so that every branch shows in the logits: the
head, the in-projection, the convolution and the three output products
wider than the rest (at N(0, 0.02) throughout x, B and C are of order
1e-3 and the state's part of `y` a millionth of `D x`), the embedding
narrower, and `D` set to 0.1 from its fixed 1 (`D_initial`, which the
reference reads too), so that the state carries a tenth of the mixer's
output rather than a hundredth.  `A_log` and `dt_bias` are the fixed
arrays of the configuration's ranges.
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
from benchmark.lib import reference_falcon_h1 as ref  # noqa: E402
from paddle_tpu import monitor  # noqa: E402
from paddle_tpu.models import (FalconH1ForCausalLM, LayerSpec,  # noqa: E402
                               StateSpec, falcon_h1_test_config)
from paddle_tpu.models.falcon_h1 import fixed_ssm_arrays  # noqa: E402
from paddle_tpu.ops import mamba2_ssd as ssd  # noqa: E402
from paddle_tpu.ops import pallas_ops as po  # noqa: E402
from paddle_tpu.serving import EngineConfig, LLMEngine  # noqa: E402
from paddle_tpu.serving.scheduler import SamplingParams  # noqa: E402

CHUNK = 16      # the test configuration's mamba_chunk_size
# float32 on both sides, logits of standard deviation 0.13: the chunked
# form and flash sum in another order than the reference's recurrence and
# blocked softmax (2e-7 read); a state rounded to bfloat16 moves a logit by
# 1e-3, one not carried or a convolution without its history by tenths
LOGIT_ATOL = 2e-5
# the seeded weights' standard deviations, by name; 0.08 elsewhere
STD = {"embed": 0.02, "head": 2.0, "o_w": 0.5, "out_proj_w": 0.3,
       "mlp_down_w": 1.0, "conv_w": 1.0, "in_proj_w": 0.5}


def _seeded(cfg, seed=0):
    model = FalconH1ForCausalLM(cfg)
    rng = np.random.default_rng(seed)
    for n, p in model.named_parameters():
        kind = n.rsplit("_", 1)[0] if n[-1].isdigit() else n
        if "norm" in n:
            val = 1 + 0.1 * rng.standard_normal(p.shape)
        else:
            val = STD.get(kind, 0.08) * rng.standard_normal(p.shape)
        p._data = jnp.asarray(val, p._data.dtype)
    return model


# the skip term's weight of the seeded models: the state's share of `y`
D_SEEDED = 0.1


@pytest.fixture(scope="module")
def tiny():
    cfg = falcon_h1_test_config(D_initial=D_SEEDED)
    return _seeded(cfg), cfg


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


# every sequence the reference reads is padded to this length, so that it
# compiles once a fault (every form is causal: what follows a position
# changes nothing at it)
REF_LEN = 160


def _reference_logits(model, cfg, seq, fault=None):
    ids = np.zeros((REF_LEN,), np.int32)
    ids[:len(seq)] = seq
    return np.asarray(ref.logits(ref.params_from_model(model),
                                 jnp.asarray(ids), _cfg_dict(cfg),
                                 fault))[:len(seq)]


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
    try:
        outs = eng.generate(prompts,
                            SamplingParams(max_new_tokens=new_tokens))
    finally:
        eng._dispatch_sampler = inner
    return outs, [np.stack(seen[i]) for i in sorted(seen)]


@pytest.fixture(scope="module")
def engines(tiny):
    """One engine a token budget, shared by the cases that ask for it."""
    made = {}

    def get(budget):
        if budget not in made:
            made[budget] = _engine(tiny[0], max_num_batched_tokens=budget)
        return made[budget]
    return get


def _memories(eng, rid):
    """Everything the engine keeps of request `rid`, on the host: its K/V
    rows in table order, its convolution state and its SSM state, a layer
    each."""
    kv = eng.caches["full"]
    n = eng._requests[rid].total_len - 1
    slots = [kv.slot(rid, p) for p in range(n)]
    out = {"k": [np.asarray(b.reshape(-1, b.shape[-1])[np.array(slots)])
                 for b in kv.k_blocks],
           "v": [np.asarray(b.reshape(-1, b.shape[-1])[np.array(slots)])
                 for b in kv.v_blocks]}
    for g, st in eng.states.items():
        out[g] = [np.asarray(s[st.slot_of(rid)]) for s in st.state]
    return out


# -- (i) the dense forward against the reference ------------------------------

def test_forward_matches_reference_logits(tiny):
    """Whole sequences through flash, the convolution from zeros and the
    chunked SSD from a zero state, every logit."""
    model, cfg = tiny
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 150))
    got = np.asarray(model(jnp.asarray(ids))._data)
    for row, g in zip(ids, got):
        want = _reference_logits(model, cfg, row)
        assert want.std() > 0.1
        np.testing.assert_allclose(g, want, atol=LOGIT_ATOL, rtol=0)


@pytest.mark.parametrize("fault", ["bf16_state", "no_ssm_carry",
                                   "no_conv_history", "no_attention",
                                   "fp8"])
def test_a_dropped_memory_shows_in_the_logits(tiny, fault):
    """What LOGIT_ATOL is set against: the same tokens under each of the
    reference's faults move a logit by ten times as much or more."""
    model, cfg = tiny
    seq = _prompts(cfg, (150,))[0]
    sound = _reference_logits(model, cfg, seq)
    moved = _reference_logits(model, cfg, seq, fault)
    assert np.abs(moved - sound).max() > 10 * LOGIT_ATOL, fault


def test_fixed_arrays_are_mamba2s_ranges(tiny):
    """A over [1, 16], dt at a zero input over [0.001, 0.1], D 1 by
    default; none of the three is a parameter the harness draws, and the
    reference makes the same three from the configuration on its own."""
    model, _ = tiny
    names = {n for n, _ in model.named_parameters()}
    assert not names & {"A_log", "dt_bias", "D"}
    cfg = falcon_h1_test_config()
    made = [np.asarray(a) for a in fixed_ssm_arrays(cfg)]
    a_log, dt_bias, d = made
    np.testing.assert_allclose(np.exp(a_log)[[0, -1]], [1.0, 16.0],
                               rtol=1e-6)
    dt = np.asarray(jax.nn.softplus(jnp.asarray(dt_bias)))
    np.testing.assert_allclose(dt[[0, -1]], [1e-3, 1e-1], rtol=1e-5)
    assert (np.diff(dt) > 0).all() and (d == 1).all()
    for own, theirs in zip(made, ref.fixed_arrays(_cfg_dict(cfg))):
        np.testing.assert_allclose(own, theirs, rtol=1e-5, atol=1e-6)
    held = model.param_arrays()
    assert len(held["A_log"]) == model.cfg.num_hidden_layers
    np.testing.assert_array_equal(held["D"][0], np.float32(D_SEEDED))


# -- (ii) prefill, then decode, through the engine ----------------------------

@pytest.mark.parametrize("lens,budget", [
    ((CHUNK - 1, CHUNK), None), ((CHUNK + 1, 3 * CHUNK + 5), None),
    ((1, 2, 9), None), ((70,), 24), ((40,), CHUNK + 1)],
    ids=["mid-chunk-and-on-its-boundary", "over-a-chunk-and-three",
         "short", "continuation-of-24", "continuation-over-a-chunk"])
def test_engine_serves_the_reference_logits(tiny, engines, lens, budget):
    """Every sampled token's logits against the reference's full forward
    over prompt + served tokens: the prompt through `prefill(P)` (or, under
    a token budget, chunked-prefill continuations that carry all three
    memories in), the tokens through the decode program's K/V, convolution
    and in-place SSM update, a mixed batch with padding rows."""
    model, cfg = tiny
    prompts = _prompts(cfg, lens)
    eng = engines(budget)
    assert list(eng.caches) == ["full"] and list(eng.states) == [
        "conv", "ssm"]
    assert not eng._mix
    outs, logits = _served_logits(eng, prompts, 10)
    for p, o, got in zip(prompts, outs, logits):
        assert len(o) == len(p) + 10 and got.shape[0] == 10
        want = _reference_logits(model, cfg, o)[len(p) - 1:-1]
        np.testing.assert_allclose(got, want, atol=LOGIT_ATOL, rtol=0)
    assert all(st.slots_in_use == 0 for st in eng.states.values())
    assert eng.cache.blocks_in_use == 0


def test_a_layer_names_three_specs(tiny):
    model, cfg = tiny
    eng = _engine(model)
    specs = eng.form.layer_specs
    assert len(specs) == cfg.num_hidden_layers
    attn, conv, state = specs[0]
    assert isinstance(attn, LayerSpec) and attn.group == "full"
    assert isinstance(conv, StateSpec) and not conv.in_place
    assert conv.shape == (3, cfg.conv_dim)
    assert isinstance(state, StateSpec) and state.in_place
    assert state.shape == ssd.state_shape(4, 16, 16)
    # three pools a layer, in the specs' order: K, V, conv, SSM
    kv = eng._kv_flat()
    assert len(kv) == 4 * cfg.num_hidden_layers
    assert kv[2].shape == (5, 3, cfg.conv_dim)
    assert kv[3].shape == (5, 4, 16, 16) and kv[3].dtype == jnp.float32


# -- (iii) one function, three forms ------------------------------------------

def _ssd_inputs(b, t, h, g, p, n, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((b, t, h, p)), dtype)
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                        (b, t, h))), jnp.float32)
    a = -jnp.asarray(np.linspace(1.0, 16.0, h), jnp.float32)
    bb, cc = (jnp.asarray(rng.standard_normal((b, t, g, n)), dtype)
              for _ in range(2))
    return x, dt, a, bb, cc


def _recurrent(x, dt, a, bb, cc):
    """The recurrence a position at a time in numpy float64, the state
    `[B, H, N, P]` as the program lays it -> (y [B,T,H,P], the state after
    the last position)."""
    x, dt, a, bb, cc = (np.asarray(v, np.float64) for v in (x, dt, a, bb,
                                                              cc))
    b, t, h, p = x.shape
    group = np.arange(h) // (h // bb.shape[2])
    state = np.zeros((b, h, bb.shape[-1], p))
    y = np.zeros((b, t, h, p))
    for i in range(t):
        state = (np.exp(dt[:, i] * a)[..., None, None] * state
                 + bb[:, i][:, group][..., None]
                 * (dt[:, i, :, None] * x[:, i])[:, :, None])
        y[:, i] = np.einsum("bhn,bhnp->bhp", cc[:, i][:, group], state)
    return y, state


@pytest.mark.parametrize("t", [1, 7, 8, 3 * 8 + 5],
                         ids=["one", "chunk-1", "chunk", "3-chunks+5"])
def test_recurrent_and_chunked_forms_agree(t):
    x, dt, a, bb, cc = _ssd_inputs(2, t, 4, 2, 8, 16, seed=t)
    want, s_want = _recurrent(x, dt, a, bb, cc)
    got, s_got = ssd.ssd_chunked(x, dt, a, bb, cc, chunk=8)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.abs(want).max())
    np.testing.assert_allclose(s_got, s_want, rtol=0,
                               atol=1e-5 * np.abs(s_want).max())
    assert s_want.shape == (2,) + ssd.state_shape(4, 8, 16)


def test_the_chunked_form_carries_a_state_in():
    x, dt, a, bb, cc = _ssd_inputs(1, 40, 4, 2, 8, 16, seed=3)
    whole, s_whole = ssd.ssd_chunked(x, dt, a, bb, cc, chunk=8)
    cut = 13
    _, s0 = ssd.ssd_chunked(x[:, :cut], dt[:, :cut], a, bb[:, :cut],
                            cc[:, :cut], chunk=8)
    rest, s1 = ssd.ssd_chunked(x[:, cut:], dt[:, cut:], a, bb[:, cut:],
                               cc[:, cut:], s0, chunk=8)
    np.testing.assert_allclose(rest, whole[:, cut:], rtol=0,
                               atol=1e-5 * np.abs(whole).max())
    np.testing.assert_allclose(s1, s_whole, rtol=0,
                               atol=1e-5 * np.abs(s_whole).max())


# -- (iv) each memory forked, swapped, exported, preempted --------------------

def test_fork_copies_every_memory_and_decodes_the_parents_tokens(tiny):
    """The child starts from a copy of the parent's convolution and SSM
    states (other slots) and shares its K/V blocks, and greedy, it decodes
    the tokens the parent decodes."""
    model, cfg = tiny
    eng = _engine(model)
    prompt = _prompts(cfg, (37,), seed=2)[0]
    parent = eng.add_request(prompt, SamplingParams(max_new_tokens=12))
    for _ in range(4):
        eng.step()
    child = eng.fork_request(parent, SamplingParams(max_new_tokens=6))
    got, want = _memories(eng, child), _memories(eng, parent)
    for group in ("conv", "ssm"):
        st = eng.states[group]
        assert st.slot_of(child) != st.slot_of(parent)
        for c, p in zip(got[group], want[group]):
            np.testing.assert_array_equal(c, p)
    for name in ("k", "v"):
        for c, p in zip(got[name], want[name]):
            np.testing.assert_array_equal(c, p)
    fork_at = len(eng._requests[parent].output_ids)
    while eng.has_unfinished():
        eng.step()
    p_out, c_out = eng.request_output(parent), eng.request_output(child)
    n = len(prompt) + fork_at
    assert list(c_out[:n]) == list(p_out[:n])
    assert list(c_out[n:]) == list(p_out[n:n + 6])


def test_swap_out_and_in_restore_every_memory_bit_for_bit(tiny):
    """A sequence's K/V, convolution state and SSM state off the device
    and back, into whatever blocks and slots are free, after another
    sequence has dirtied the old ones."""
    model, cfg = tiny
    eng = _engine(model, max_num_seqs=2)
    a = eng.add_request(_prompts(cfg, (20,))[0],
                        SamplingParams(max_new_tokens=4))
    eng.step()
    eng.settle()
    before = _memories(eng, a)
    assert all(np.abs(x).max() > 0 for g in before.values() for x in g)
    old = {g: st.slot_of(a) for g, st in eng.states.items()}
    saved = eng.kv.swap_out(a)
    assert all(st.slots_in_use == 0 for st in eng.states.values())
    eng.kv.allocate("other", 20)
    for g, st in eng.states.items():
        assert st.slot_of("other") == old[g]
        st.state = [s.at[old[g]].set(1.0) for s in st.state]
    assert eng.kv.can_swap_in(saved)
    eng.kv.swap_in(a, saved)
    after = _memories(eng, a)
    for g in before:
        for x, y in zip(before[g], after[g]):
            np.testing.assert_array_equal(x, y)


def test_preemption_resumes_to_the_same_tokens(tiny):
    """A K/V pool too small for three sequences at once: the scheduler
    evicts (all three memories to the host) and resumes, and every
    request decodes what it decodes alone."""
    model, cfg = tiny
    prompts = _prompts(cfg, (40, 40, 40), seed=5)
    sp = SamplingParams(max_new_tokens=20)
    want = _engine(model).generate(prompts, sp)
    eng = _engine(model, num_blocks=10)
    got = eng.generate(prompts, sp)
    assert eng.scheduler.num_evictions > 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_export_and_adopt_decode_on_from_every_memory(tiny):
    """A request exported mid-decode ships its K/V and both states and
    decodes on in another engine to the tokens of an engine it never
    left."""
    model, cfg = tiny
    prompt = _prompts(cfg, (50,), seed=9)[0]
    sp = SamplingParams(max_new_tokens=9)
    src, dst = _engine(model), _engine(model)
    want, = dst.generate([prompt], sp)
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
    assert all(st.slots_in_use == 0 for st in src.states.values())


@pytest.mark.parametrize("option", [
    {"kv_cache_dtype": "int8"}, {"speculative_tokens": 2},
    {"enable_prefix_caching": True}], ids=lambda o: next(iter(o)))
def test_options_it_does_not_carry_raise_by_name(tiny, option):
    model, _ = tiny
    with pytest.raises(ValueError, match=next(iter(option))):
        LLMEngine(model, EngineConfig(block_size=16, max_model_len=32,
                                      **option))


# -- (v) the kernels, in interpret mode ---------------------------------------

@pytest.fixture
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()


def _pool(slots, h, g, p, n, seed):
    """A pool whose every slot holds a state a prefill could have left,
    and the dropped slot zeros."""
    pool = [ssd.ssd_chunked(*_ssd_inputs(1, 12, h, g, p, n, seed + i),
                            chunk=12)[1][0] for i in range(slots)]
    return jnp.stack(pool + [jnp.zeros_like(pool[0])])


def test_decode_kernel_equals_the_xla_form(_interpret_mode):
    """Three rows over 16 heads of 128 in 2 groups of a 128-wide state,
    one a padding row on the dropped slot: outputs and the states written
    (float32 sums in another order), the slot nobody names untouched, the
    dropped slot left as it was."""
    pool = _pool(3, 16, 2, 128, 128, seed=0)
    x, dt, a, bb, cc = (v[:, 0] if v.ndim > 1 else v
                        for v in _ssd_inputs(3, 1, 16, 2, 128, 128, 5))
    slots = jnp.asarray([2, 0, 3], jnp.int32)
    valid = jnp.asarray([True, True, False])
    y, new = ssd.ssd_decode(x, dt, a, bb, cc, pool, slots, valid)
    assert po.attention_path_counts().get("ssd_decode_kernel") == 1
    want_y, want_s = ssd._decode_math(x, dt, a, bb, cc, pool[slots])
    np.testing.assert_allclose(y[:2], want_y[:2], rtol=0,
                               atol=1e-5 * np.abs(want_y[:2]).max())
    np.testing.assert_allclose(new[slots[:2]], want_s[:2], rtol=0,
                               atol=1e-5 * np.abs(want_s).max())
    np.testing.assert_array_equal(new[1], pool[1])
    assert not np.asarray(new[3]).any()


@pytest.mark.parametrize("t,fresh", [(128, True), (300, False)],
                         ids=["fresh-one-chunk", "carried-three-chunks"])
def test_prefill_kernel_equals_the_xla_form(_interpret_mode, t, fresh):
    pool = _pool(2, 16, 2, 128, 128, seed=1)
    x, dt, a, bb, cc = _ssd_inputs(1, t, 16, 2, 128, 128, seed=t,
                                   dtype=jnp.bfloat16)
    slot = jnp.asarray([1], jnp.int32)
    y, new = ssd.ssd_prefill(x, dt, a, bb, cc, pool, slot, fresh)
    assert po.attention_path_counts().get("ssd_prefill_kernel") == 1
    want_y, want_s = ssd.ssd_chunked(x, dt, a, bb, cc,
                                     None if fresh else pool[slot], chunk=64)
    np.testing.assert_allclose(y, want_y, rtol=0,
                               atol=1e-5 * np.abs(want_y).max())
    np.testing.assert_allclose(new[slot], want_s, rtol=0,
                               atol=1e-5 * np.abs(want_s).max())
    np.testing.assert_array_equal(new[0], pool[0])
    np.testing.assert_array_equal(new[2], pool[2])


def test_engine_takes_both_kernels(_interpret_mode):
    """Through the engine in interpret mode at mixer heads of 128 over a
    128-wide state in chunks of 128: the prompt counts the prefill kernel
    in every layer, decode the decode kernel, no SSD path falls back, and
    the tokens' logits are the reference's (float32 products at the
    highest precision in both kernels)."""
    cfg = falcon_h1_test_config(mamba_n_heads=8, mamba_d_head=128,
                                mamba_d_ssm=1024, mamba_d_state=128,
                                mamba_n_groups=1, mamba_chunk_size=128,
                                D_initial=D_SEEDED)
    model = _seeded(cfg, seed=4)
    prompts = _prompts(cfg, (130,), seed=2)
    eng = _engine(model, max_num_seqs=2)
    outs, logits = _served_logits(eng, prompts, 3)
    counts = po.attention_path_counts()
    assert counts.get("ssd_prefill_kernel") == 2
    assert counts.get("ssd_decode_kernel") == 2
    assert not [k for k in counts if k.startswith("ssd_fallback")], counts
    want = _reference_logits(model, cfg, outs[0])[len(prompts[0]) - 1:-1]
    np.testing.assert_allclose(logits[0], want, atol=LOGIT_ATOL, rtol=0)


# -- spans and counters -------------------------------------------------------

def test_ssd_counters_and_scopes(tiny):
    model, cfg = tiny
    eng = _engine(model)

    def val(name, **labels):
        return monitor.snapshot().get(name, {}).get(
            ",".join(f"{k}={v}" for k, v in sorted(labels.items())), 0)

    ssm, conv = eng.states["ssm"], eng.states["conv"]
    assert val("serving/state_bytes", group="ssm") == ssm.pool_bytes \
        == 2 * 5 * 4 * 16 * 16 * 4
    assert val("serving/state_bytes", group="conv") == conv.pool_bytes
    before = {ph: val("serving/ssd_tokens", phase=ph)
              for ph in ("prefill", "decode")}
    steps0 = val("serving/state_slot_steps", group="ssm")
    rid = eng.add_request(_prompts(cfg, (7,))[0],
                          SamplingParams(max_new_tokens=4))
    eng.step()
    assert val("serving/state_slots_in_use", group="ssm") == 1
    while eng.has_unfinished():
        eng.step()
    eng.release_request(rid)
    # a prompt of 7 and 3 decode steps of one row, through 2 layers
    assert val("serving/ssd_tokens", phase="prefill") \
        - before["prefill"] == 7 * 2
    assert val("serving/ssd_tokens", phase="decode") \
        - before["decode"] == 3 * 2
    assert val("serving/state_slot_steps", group="ssm") - steps0 == 3
    toks, pos0, lens, tables, slots, srows = eng._decode_inputs([], [], 4, 1)
    assert len(tables) == 1 and len(srows) == 2
    scopes = ("mamba2/in_proj", "mamba2/conv", "mamba2/ssd",
              "mamba2/gate_norm", "mamba2/out", "falcon_h1/mlp", "attn/full")
    text = eng._get_ragged_exec(4, 1).lower(
        eng._param_arrays(), eng._kv_flat(), toks, pos0, lens, tables,
        slots, srows).as_text(debug_info=True)
    assert all(s in text for s in scopes), [s for s in scopes
                                            if s not in text]
    one = (np.zeros((1,), np.int32),) * 2
    text = eng._get_prefill_exec(7).lower(
        eng._param_arrays(), eng._kv_flat(), np.zeros((1, 7), np.int32),
        (np.zeros((1, 7), np.int32),), one).as_text(debug_info=True)
    assert all(s in text for s in scopes)
