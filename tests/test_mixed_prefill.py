"""A whole prompt's prefill step carries the decode rows: where every layer
of a model's serving form is plain K/V attention (`LayerSpec`: GPT and
afmoe), `Scheduler.mix_decode` hands a whole prompt's prefill the running
rows too, and the engine's `prefill(P)` program runs the prompt and one
token of each row as one sequence - flash over the prompt, the ragged call
over the rows.  Here: when the scheduler mixes and when it does not, the
tokens of a loaded engine against `generate()`, the program set a lone
request of each prompt length compiles, the families that never mix, and
`serving/prefill_riders`.  Nothing here is a measurement."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import GPTForCausalLM, gpt_test_config
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from paddle_tpu.serving.kv_cache import BlockKVCache, prefix_block_keys
from paddle_tpu.serving.scheduler import Request, Scheduler

import jax.numpy as jnp


# -- the scheduler --------------------------------------------------------------

def _running(sched, rid, prompt_len=6, new=8):
    """Admit a request through `schedule()` (carrying nothing: the rows
    are set up one by one) and finish its prefill as the engine would: its
    prompt computed, its first token recorded."""
    mix, sched.mix_decode = sched.mix_decode, False
    req = Request(rid, list(range(1, prompt_len + 1)),
                  SamplingParams(max_new_tokens=new))
    sched.add(req)
    out = sched.schedule()
    sched.mix_decode = mix
    assert out.kind == "prefill" and out.prefill_request is req
    req.num_computed = req.prompt_len
    req.record_token(7)
    return req


def _whole_beside_rows(sched):
    rows = [_running(sched, f"r{i}") for i in range(2)]
    sched.add(Request("new", list(range(1, 10)), SamplingParams(4)))
    return sched.schedule(), tuple(rows)


def _alone(sched):
    sched.add(Request("new", list(range(1, 10)), SamplingParams(4)))
    return sched.schedule(), ()


def _asking_no_token(sched):
    # a prompt that samples nothing (`max_new_tokens` 0) has no sampler
    # call for its riders to share
    _running(sched, "r0")
    sched.add(Request("new", list(range(1, 10)), SamplingParams(0)))
    return sched.schedule(), ()


def _owing_its_last(sched):
    done, going = (_running(sched, r, new=n) for r, n in (("a", 2), ("b", 8)))
    done.owed = 1                  # its last token is in flight
    sched.add(Request("new", list(range(1, 10)), SamplingParams(4)))
    return sched.schedule(), (going,)


def _needing_an_eviction(sched):
    # a row at the end of its block: its next slot needs a block the pool
    # has no more of once the prompt is admitted; it waits for a decode
    # step, where it may evict
    full = _running(sched, "full", prompt_len=4)     # position 4 next
    room = _running(sched, "room", prompt_len=5)
    free = sched.cache.num_free_blocks
    sched.add(Request("new", list(range(1, 4 * free + 1)), SamplingParams(1)))
    out = sched.schedule()
    assert out.kind == "prefill" and sched.cache.num_free_blocks == 0
    assert full in sched.running
    return out, (room,)


def _chunk(sched):
    _running(sched, "r0", prompt_len=3)
    sched.add(Request("long", list(range(1, 11)), SamplingParams(4)))
    first = sched.schedule()
    assert first.chunk_len == 4 < first.prefill_request.prompt_len
    assert first.decode_requests == ()
    first.prefill_request.num_computed = 4
    return sched.schedule(), ()         # the continuation


def _prefix_hit(sched):
    owner = _running(sched, "owner", prompt_len=9)
    owner.prefix_keys = prefix_block_keys(owner.prompt_ids, 4)
    sched.cache.register_prefix("owner", owner.prefix_keys, 9)
    hit = Request("hit", owner.prompt_ids[:8] + [50, 51, 52],
                  SamplingParams(4))
    hit.prefix_keys = prefix_block_keys(hit.prompt_ids, 4)
    sched.add(hit)
    out = sched.schedule()
    assert out.chunk_start == 8 == hit.prefix_hit_tokens
    return out, ()


def _swap_in(sched):
    keep, back = _running(sched, "keep"), _running(sched, "back")
    back.swap = sched.cache.swap_out("back")
    back.state = Request.PREEMPTED
    sched.running.remove(back)
    sched.waiting.appendleft(back)
    out = sched.schedule()
    assert back in sched.running and back.swap is None
    return out, (keep, back)           # a decode step, no prefill


def _speculating(sched):
    rows = [_running(sched, f"r{i}") for i in range(2)]
    sched.add(Request("new", list(range(1, 10)), SamplingParams(4)))
    return sched.schedule(), tuple(rows)


_CASES = {
    # case: (the scheduler's keywords, scenario, kind of the step decided,
    # whether the scenario's rows ride it)
    "whole-prompt": ({}, _whole_beside_rows, "prefill", True),
    "nothing-running": ({}, _alone, "prefill", True),
    "row-owes-its-last": ({}, _owing_its_last, "prefill", True),
    "row-needs-an-eviction": ({"num_blocks": 6}, _needing_an_eviction,
                              "prefill", True),
    "chunk": ({"max_num_batched_tokens": 4}, _chunk, "prefill", False),
    "prompt-asks-no-token": ({}, _asking_no_token, "prefill", False),
    "prefix-hit": ({}, _prefix_hit, "prefill", False),
    "swap-in": ({}, _swap_in, "decode", False),
    "spec-tokens": ({"spec_tokens": 2}, _speculating, "prefill", False),
}


@pytest.mark.parametrize("case", sorted(_CASES))
def test_scheduler_mixes_only_a_whole_prompt_with_running_rows(case):
    """A whole prompt admitted while rows decode carries every row whose
    next slot needs no eviction (and that does not owe its last token);
    a chunk or its continuation, a prefix hit's tail and a swap-in carry
    none, and with `spec_tokens > 0` nothing is ever mixed: the prompt is
    a step of its own and the rows decode after it."""
    kw, scenario, kind, mixes = _CASES[case]
    kw = dict(kw)
    cache = BlockKVCache(num_layers=1, num_blocks=kw.pop("num_blocks", 64),
                         block_size=4, num_heads=1, head_dim=2)
    sched = Scheduler(cache, max_num_seqs=4, mix_decode=True,
                      **dict({"max_num_batched_tokens": 64}, **kw))
    out, rows = scenario(sched)
    assert out.kind == kind
    if kind == "prefill":
        assert out.decode_requests == (rows if mixes else ())
        for r in out.decode_requests:  # each one slot further
            assert cache.can_grow_to(r.req_id, r.total_len)
    else:                              # the rows decode in a step of
        assert out.decode_requests == rows   # their own
    assert sched.mix_decode == (case != "spec-tokens")


# -- the engine -----------------------------------------------------------------

def _varied(m, std):
    """`m` with its weights N(0, std) (afmoe's norm scales near 1, biases
    as made): tokens that vary from one position to the next (the
    initialisers' weights repeat a token, which hides a rider fed the
    wrong one)."""
    rng = np.random.default_rng(0)
    for n, p in m.named_parameters():
        if n.endswith(("_b", ".bias", "expert_bias")):
            continue
        val = (1 + 0.1 * rng.standard_normal(p.shape) if "norm" in n
               else std * rng.standard_normal(p.shape))
        p._data = jnp.asarray(val, p._data.dtype)
    m.eval()
    return m


@pytest.fixture(scope="module")
def gpt():
    return _varied(GPTForCausalLM(gpt_test_config(
        stacked_blocks=True, sequence_parallel=False)), 0.2)


@pytest.fixture(scope="module")
def afmoe():
    from paddle_tpu.models import AfmoeForCausalLM, afmoe_test_config

    return _varied(AfmoeForCausalLM(afmoe_test_config()), 0.08)


_FAMILIES = {"gpt": dict(block_size=16), "afmoe": dict(block_size=4,
                                                       max_model_len=64)}
_LENS = (5, 9, 13)
NEW = 8


@pytest.fixture(params=sorted(_FAMILIES))
def mixing(request, gpt, afmoe):
    """(model, EngineConfig keywords) of a family that mixes."""
    return {"gpt": gpt, "afmoe": afmoe}[request.param], _FAMILIES[
        request.param]


def _prompts(vocab, lens=_LENS, seed=4):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (n,)).astype(np.int32) for n in lens]


def _staggered(eng, prompts, new=NEW):
    """Requests arriving two steps apart, so each prompt finds rows
    decoding -> ({prompt index: tokens}, riders carried)."""
    riders = []
    inner = eng.scheduler.schedule

    def schedule():
        out = inner()
        if out.kind == "prefill":
            riders.append(len(out.decode_requests))
        return out

    eng.scheduler.schedule = schedule
    rids, pending = {}, list(enumerate(prompts))
    while pending or eng.has_unfinished():
        if pending:
            i, p = pending.pop(0)
            rids[eng.add_request(p, SamplingParams(max_new_tokens=new))] = i
        for _ in range(2):
            if eng.has_unfinished():
                eng.step()
    outs = {i: eng.request_output(r) for r, i in rids.items()}
    for r in rids:
        eng.release_request(r)
    return outs, riders


def test_loaded_engine_greedy_tokens_equal_generate(mixing):
    """Prompts that arrive while rows decode take mixed steps, and every
    request's greedy tokens are those of `generate()`: the dense path's
    for GPT, the engine's own for afmoe (each prompt alone, whose prefill
    carries nothing)."""
    model, kw = mixing
    prompts = _prompts(model.cfg.vocab_size)
    eng = LLMEngine(model, EngineConfig(max_num_seqs=4, **kw))
    got, riders = _staggered(eng, prompts)
    assert riders[0] == 0 and all(riders[1:]), riders
    for i, p in enumerate(prompts):
        if isinstance(model, GPTForCausalLM):
            want = np.asarray(model.generate(
                Tensor(jnp.asarray(p[None])), max_new_tokens=NEW)._data)[0]
        else:
            want = LLMEngine(model, EngineConfig(max_num_seqs=4, **kw)) \
                .generate([p], SamplingParams(max_new_tokens=NEW))[0]
            # and each token its position's largest logit of the model's
            # whole-sequence forward, up to float32 near-ties
            logits = np.asarray(model.forward_arrays(
                model.param_arrays(), jnp.asarray(got[i][None])))[0]
            rows = logits[len(p) - 1:-1]
            assert (rows.max(-1) - rows[np.arange(NEW), got[i][len(p):]]
                    <= 1e-3).all(), f"prompt {i}"
        np.testing.assert_array_equal(got[i], want, err_msg=f"prompt {i}")


def test_a_lone_request_a_length_compiles_what_a_loaded_loop_runs(mixing):
    """One request of each prompt length alone compiles every program a
    loaded loop of those lengths runs: the prefill that carries rows is
    the one that carries none, and its riders sample with the decode
    step's sampler, so nothing compiles once the load comes."""
    model, kw = mixing
    eng = LLMEngine(model, EngineConfig(max_num_seqs=4, **kw))
    for p in _prompts(model.cfg.vocab_size):
        eng.generate([p], SamplingParams(max_new_tokens=3))
    warm = set(eng._jit_cache)
    assert warm == {("prefill", n) for n in _LENS} | {
        ("ragged", 4, 1), ("feed", 4), ("sample", 4)}
    _, riders = _staggered(eng, _prompts(model.cfg.vocab_size, _LENS * 3,
                                         seed=5))
    assert sum(riders) > 0 and set(eng._jit_cache) == warm


def _other_forms():
    from paddle_tpu.models import (BrumbyForCausalLM, KeyeForCausalLM,
                                   Lfm2MoeForCausalLM, Mistral4ForCausalLM,
                                   brumby_test_config, keye_test_config,
                                   lfm2_test_config, mistral4_test_config)

    return {"lfm2": (Lfm2MoeForCausalLM, lfm2_test_config, 4),
            "mistral4": (Mistral4ForCausalLM, mistral4_test_config, 4),
            "brumby": (BrumbyForCausalLM, brumby_test_config, 16),
            "keye": (KeyeForCausalLM, keye_test_config, 4)}


@pytest.mark.parametrize("family", ["brumby", "keye", "lfm2", "mistral4"])
def test_forms_with_state_latent_or_sparse_layers_never_mix(family):
    """A form with a state, latent or sparse layer keeps every prompt a
    step of its own, and its engine compiles the programs it always has:
    a prefill a prompt length, the one-row sampler after it, the decode
    program, its feed and its sampler."""
    cls, config, bs = _other_forms()[family]
    paddle.seed(0)
    model = cls(config())
    model.eval()
    eng = LLMEngine(model, EngineConfig(block_size=bs, max_num_seqs=4,
                                        max_model_len=96))
    assert not eng._mix and not eng.scheduler.mix_decode
    _, riders = _staggered(eng, _prompts(model.cfg.vocab_size), new=4)
    assert riders == [0] * len(_LENS)
    assert set(eng._jit_cache) == {("prefill", n) for n in _LENS} | {
        ("ragged", 4, 1), ("feed", 4), ("sample", 1), ("sample", 4)}


def test_prefill_riders_counts_the_rows_a_prefill_carried(gpt):
    """Three requests at once: three prefill steps carrying 0, 1 and 2
    rows (`serving/prefill_riders` 3), observed as prefill steps; the
    riders' tokens are not decode steps' tokens (`serving/decode_tokens`
    counts those alone, so `batch_occupancy.serve` keeps its meaning)."""
    monitor.enable(True)
    try:
        snap0 = monitor.snapshot()
        eng = LLMEngine(gpt, EngineConfig(block_size=16, max_num_seqs=4))
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=NEW))
                for p in _prompts(gpt.cfg.vocab_size)]
        while eng.has_unfinished():
            eng.step()
        snap = monitor.snapshot()

        def gained(name, label=None):
            def value(s):
                v = s.get(name, 0)
                v = v.get(label, 0) if label else v
                return v["count"] if isinstance(v, dict) else v
            return value(snap) - value(snap0)

        assert gained("serving/prefill_riders") == 0 + 1 + 2
        assert gained("serving/step_time", "phase=prefill") == 3
        decode_steps = gained("serving/step_time", "phase=decode")
        # the first request's 8 tokens: its prefill's, two as a rider, five
        # decode steps; the third's: its prefill's and 7 decode steps
        assert decode_steps == NEW - 1
        assert gained("serving/decode_tokens") == 3 * NEW - 3 - 3
        for r in rids:
            assert len(eng.request_output(r)) > NEW
            eng.release_request(r)
    finally:
        monitor.refresh()
