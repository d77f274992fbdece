"""The engine's sample program (`serving.engine._sample_program`): what a
row gets is `models.gpt._sample_next`'s token and key on that row alone,
whatever else the batch holds; what the program RUNS follows the batch
(no vocabulary sort unless a sampling row truncates, then one a row)."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import GPTForCausalLM, gpt_test_config
from paddle_tpu.models.gpt import _sample_next
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams
from paddle_tpu.serving.engine import (_NEG_INF, _SAMPLER_PATHS,
                                       _sample_program, _sampler_path)

# kind -> (do_sample, temperature, top_k, top_p, the path a batch of it takes)
KINDS = {
    "greedy": (False, 1.0, 0, 1.0, "argmax"),
    "temperature": (True, 0.7, 0, 1.0, "categorical"),
    "top_k": (True, 0.9, 12, 1.0, "truncated"),
    "top_p": (True, 1.1, 0, 0.85, "truncated"),
    "both": (True, 0.8, 20, 0.9, "truncated"),
    # serving/api.py passes a body's top_p through on a request with no
    # temperature: the row is greedy and truncates nothing
    "greedy_with_top_p": (False, 1.0, 0, 0.9, "argmax"),
}
PADDING = (False, 1.0, 0, 1.0)      # what _dispatch_sampler fills past rows
program = jax.jit(_sample_program)
solo = jax.jit(_sample_next, static_argnums=(2, 3, 4, 5))


def _batch(rows, vocab, seed=0, logits=None):
    """`rows`: (do_sample, temperature, top_k, top_p) a row -> the
    program's six inputs as `_dispatch_sampler` builds them."""
    rng = np.random.RandomState(seed)
    if logits is None:
        logits = (3.0 * rng.randn(len(rows), vocab)).astype(np.float32)
    keys = np.stack([np.asarray(jax.random.PRNGKey(100 + seed + i), np.uint32)
                     for i in range(len(rows))])
    for i, r in enumerate(rows):
        if r is PADDING:
            keys[i] = 0
    ds, temp, topk, topp = (np.asarray(c, dt) for c, dt in zip(
        zip(*rows), (bool, np.float32, np.int32, np.float32)))
    return logits, keys, ds, temp, topk, topp


def _solo_row(l, key, ds, t, k, p):
    """What generate() does with this row alone (gpt.py's decode loop)."""
    if not ds:
        return int(solo(l[None], None, False, 1.0, 0, 1.0)[0]), key
    new_key, sub = jax.random.split(jnp.asarray(key))
    tok = solo(l[None], sub, True, float(t), int(k), float(p))
    return int(tok[0]), np.asarray(new_key)


@pytest.mark.parametrize("vocab", [128, 1003])
@pytest.mark.parametrize("layout", ["uniform", "mixed"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_rows_match_solo_sampling(kind, layout, vocab):
    row = KINDS[kind][:4]
    if layout == "uniform":
        rows = [row] * 4
    else:
        rows = [row, KINDS["greedy"][:4], row, PADDING, KINDS["greedy"][:4],
                PADDING]
    inputs = _batch(rows, vocab, seed=len(kind))
    want = KINDS[kind][4]
    assert _SAMPLER_PATHS[_sampler_path(*inputs[2:3], *inputs[4:])] == want
    toks, new_keys = program(*inputs)
    for i, row in enumerate(zip(*inputs)):
        tok, key = _solo_row(*row)
        assert int(toks[i]) == tok, f"row {i}"
        np.testing.assert_array_equal(np.asarray(new_keys[i]), key,
                                      err_msg=f"row {i}")


def two_sort_row(l, key_, ds, t, k, p):
    """The row function the program replaced: an ascending sort for top-k,
    a second, descending, of the masked row for top-p, for every row
    (`scripts/onchip_checks.py --sampler` times it beside the program)."""
    l1 = l[None, :]
    greedy = jnp.argmax(l1, axis=-1).astype(jnp.int32)[0]
    new_key, sub = jax.random.split(key_)
    ll = l1 / jnp.maximum(t, jnp.float32(1e-6))
    v = ll.shape[-1]
    asc = jnp.sort(ll, axis=-1)
    kth = jnp.take_along_axis(
        asc, jnp.clip(v - k, 0, v - 1)[None, None], axis=-1)
    ll = jnp.where(k > 0, jnp.where(ll < kth, _NEG_INF, ll), ll)
    desc = jnp.sort(ll, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    thresh = jnp.min(jnp.where(cum - probs <= p, desc, jnp.inf), axis=-1,
                     keepdims=True)
    ll = jnp.where(p < 1.0, jnp.where(ll < thresh, _NEG_INF, ll), ll)
    samp = jax.random.categorical(sub, ll, axis=-1).astype(jnp.int32)[0]
    return jnp.where(ds, samp, greedy), jnp.where(ds, new_key, key_)


TIE_ROWS = [(True, 1.0, 1, 1.0), (True, 0.5, 7, 1.0), (True, 1.0, 60, 0.5),
            (True, 1.3, 64, 0.9),       # k == v
            (True, 1.0, 500, 0.7),      # k > v
            (True, 1.0, 0, 0.05), (True, 1.0, 3, 0.999), (False, 1.0, 5, 0.5)]


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_one_sort_equals_two_on_ties_and_wide_k(seed):
    """Logits drawn from five values, so the k-th largest is tied many
    times over, and `top_k` at and past the vocabulary's size."""
    v = 64
    rng = np.random.RandomState(seed)
    logits = rng.randint(-2, 3, (len(TIE_ROWS), v)).astype(np.float32)
    inputs = _batch(TIE_ROWS, v, seed=seed, logits=logits)
    # the identity itself: masking the sorted row is sorting the masked row
    for l, (_, t, k, _) in zip(logits, TIE_ROWS):
        ll = l / np.float32(t)
        desc1 = np.sort(ll)[::-1]
        kth = desc1[np.clip(k - 1, 0, v - 1)]
        assert kth == np.sort(ll)[np.clip(v - k, 0, v - 1)]
        np.testing.assert_array_equal(
            np.where(desc1 < kth, np.float32(_NEG_INF), desc1),
            np.sort(np.where(ll < kth, np.float32(_NEG_INF), ll))[::-1])
    toks, new_keys = program(*inputs)
    want_toks, want_keys = jax.jit(jax.vmap(two_sort_row))(*inputs)
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(want_toks))
    np.testing.assert_array_equal(np.asarray(new_keys),
                                  np.asarray(want_keys))


def _eqns(jaxpr):
    """Every equation of `jaxpr` and of what is nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _sorts(jaxpr):
    return [e for e in _eqns(jaxpr) if e.primitive.name == "sort"]


def test_only_the_truncating_branch_sorts_and_once():
    inputs = _batch([KINDS["both"][:4]] * 4, 1003)
    jaxpr = jax.make_jaxpr(_sample_program)(*inputs).jaxpr
    [switch] = [e for e in jaxpr.eqns if e.primitive.name == "cond"]
    # the batch-level predicate selects: a scalar index, outside any vmap
    assert switch.invars[0].aval.shape == ()
    argmax, categorical, truncated = (
        _sorts(b.jaxpr) for b in switch.params["branches"])
    assert not argmax and not categorical
    # one sort, over every row at once, and none outside the switch
    [sort] = truncated
    assert [v.aval.shape for v in sort.invars] == [(4, 1, 1003)]
    assert _sorts(jaxpr) == [sort]


@pytest.fixture(scope="module")
def engine():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_test_config(stacked_blocks=True,
                                       sequence_parallel=False))
    m.eval()
    return LLMEngine(m, EngineConfig(block_size=16, max_num_seqs=4))


@pytest.mark.parametrize("kinds,path", [
    (("greedy", "greedy_with_top_p"), "argmax"),
    (("temperature", "greedy"), "categorical"),
    (("greedy", "top_p", "temperature"), "truncated")])
def test_sampler_steps_counts_each_dispatch_on_its_path(engine, kinds, path):
    expected = collections.Counter()
    inner = engine._dispatch_sampler

    def counted(rows, logits):
        ps = [r.params for r in rows]
        if any(p.do_sample and (p.top_k > 0 or p.top_p < 1.0) for p in ps):
            expected["truncated", len(rows) > 1] += 1
        elif any(p.do_sample for p in ps):
            expected["categorical", len(rows) > 1] += 1
        else:
            expected["argmax", len(rows) > 1] += 1
        return inner(rows, logits)

    before = [c.value for c in engine._m_sampler]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, 100, (4,)).astype(np.int32) for _ in kinds]
    params = []
    for i, kind in enumerate(kinds):
        ds, t, k, p, _ = KINDS[kind]
        params.append(SamplingParams(max_new_tokens=4, do_sample=ds,
                                     temperature=t, top_k=k, top_p=p,
                                     seed=i))
    engine._dispatch_sampler = counted
    try:
        engine.generate(prompts, params)
    finally:
        del engine._dispatch_sampler
    moved = {p: c.value - b for p, c, b in zip(
        _SAMPLER_PATHS, engine._m_sampler, before)}
    # one count a dispatch (a prefill samples its one row alone, on that
    # row's own path), and the steps that held every kind took `path`
    assert moved == {p: expected[p, False] + expected[p, True]
                     for p in _SAMPLER_PATHS}
    assert expected[path, True] > 0
