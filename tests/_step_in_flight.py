"""Scenarios for the engine's step in flight (ISSUE 35), shared by the
serving test files of each family: the same requests served with a step
in flight and with every step settled (`LLMEngine.settle()` after each
`step()`) give the same tokens, the same keys and the same pools.

Every function takes `make()`, which builds a fresh engine of the family
at hand, and the family's vocabulary size.
"""
import numpy as np

from paddle_tpu import monitor
from paddle_tpu.resilience.retry import Deadline
from paddle_tpu.serving import SamplingParams

# (prompt length, tokens to generate): unequal on both sides
DECK = [(5, 9), (11, 4), (3, 12), (7, 6), (9, 1), (4, 7), (13, 5), (6, 8)]


def params(i, new, eos=None):
    """Request i of a mixed deck: even ones greedy, odd ones seeded
    sampling, one of them truncating."""
    return SamplingParams(
        max_new_tokens=new, do_sample=i % 2 == 1, temperature=0.9,
        top_k=5 if i == 3 else 0, seed=i, eos_token_id=eos)


def prompts(vocab, deck=DECK, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab, (n,)).astype(np.int32) for n, _ in deck]


def free_counts(eng):
    """What every pool of the engine has left: free blocks a cache group,
    free slots a state group."""
    return ([k.num_free_blocks for k in eng.caches.values()]
            + [st.num_slots - st.slots_in_use for st in eng.states.values()])


def counter(name):
    """{labels: value} of one counter of the monitor snapshot."""
    got = monitor.snapshot().get(name, {})
    return dict(got) if isinstance(got, dict) else {"": got}


def moved(name, before):
    """What a counter gained since `before`, zero entries left out."""
    now = counter(name)
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v - before.get(k, 0)}


def closed_loop(eng, vocab, settled, clients=3, eos=None, watch=None):
    """`clients` callers play DECK in turn, each sending its next request
    when its last one has finished.  -> ({deck index: tokens}, {deck index:
    {tokens emitted: key then}} for the requests in `watch`).
    Keys are read only where the host's copy is current: after a settle."""
    deck = list(enumerate(zip(prompts(vocab), DECK)))
    live, outs, keys = {}, {}, {}
    while deck or live:
        while deck and len(live) < clients:
            i, (p, (_, new)) = deck.pop(0)
            live[eng.add_request(p, params(i, new, eos))] = i
        eng.step()
        if settled:
            eng.settle()
            for rid, i in live.items():
                req = eng._requests[rid]
                if watch and i in watch:
                    keys.setdefault(i, {})[len(req.output_ids)] = \
                        req.key.copy()
        for rid, i in list(live.items()):
            if eng._requests[rid].finished:
                outs[i] = eng.request_output(rid)
                eng.release_request(rid)
                del live[rid]
    assert not eng.has_unfinished()
    return outs, keys


def check_tokens_and_keys(make, vocab):
    """Token for token, and key for key at `export_request`."""
    want, keys = closed_loop(make(), vocab, settled=True, watch={1, 3})
    before = counter("serving/steps_dispatched")
    got, _ = closed_loop(make(), vocab, settled=False)
    assert sorted(got) == sorted(want) == list(range(len(DECK)))
    for i in want:
        np.testing.assert_array_equal(got[i], want[i], err_msg=f"deck {i}")
    steps = moved("serving/steps_dispatched", before)
    # the loop stayed full: only the first step, and one after each time
    # every live row owed its last token at once, found nothing in flight
    assert steps["in_flight=1"] > 5 * steps.get("in_flight=0", 0)
    # a sampling row exported with a step owed: the hand-off carries the
    # key as it stands AFTER the owed token, which is what the settled
    # engine held when it had emitted as many
    eng = make()
    ps = prompts(vocab)
    rids = {i: eng.add_request(ps[i], params(i, 12)) for i in (0, 1, 3)}
    for _ in range(5):
        eng.step()
    for i in (1, 3):
        eng.step()                  # an export empties the pipeline
        assert eng._requests[rids[i]].owed == 1
        h = eng.export_request(rids[i])
        n = len(h["output_ids"])
        np.testing.assert_array_equal(h["output_ids"],
                                      want[i][len(ps[i]):][:n])
        np.testing.assert_array_equal(h["key"], keys[i][n])
    eng.release_request(rids[0])
    eng.settle()
    assert free_counts(eng) == free_counts(make())


def check_eos_mid_flight(make, vocab):
    """A row that ends on its `eos_token_id` is found out a step late: it
    emits nothing past it, and its blocks and slots go back."""
    plain, _ = closed_loop(make(), vocab, settled=True)
    p = prompts(vocab)[2]
    eos = int(plain[2][len(p) + 4])          # the fifth token of request 2
    want, _ = closed_loop(make(), vocab, settled=True, eos=eos)
    eng = make()
    got, _ = closed_loop(eng, vocab, settled=False, eos=eos)
    for i in want:
        np.testing.assert_array_equal(got[i], want[i], err_msg=f"deck {i}")
    tail = list(got[2][len(p):])
    assert tail.index(eos) == len(tail) - 1 and len(tail) < DECK[2][1]
    assert free_counts(eng) == free_counts(make())


def _run_until_owed(eng, vocab, settled, n_steps=5):
    ps = prompts(vocab)
    rids = [eng.add_request(ps[i], params(i, 12)) for i in range(3)]
    for _ in range(n_steps):
        eng.step()
        if settled:
            eng.settle()
    if not settled:
        assert all(eng._requests[r].owed == 1 for r in rids)
    return rids


def _drain(eng, rids):
    while eng.has_unfinished():
        eng.step()
    outs = [eng.request_output(r) if r in eng._requests else None
            for r in rids]
    for r in rids:
        eng.release_request(r)
    return outs


def check_cancel_and_deadline(make, vocab):
    """A cancel and a deadline that hit a row riding the step in flight
    leave the pools, and the other rows' tokens, as the settled run's."""
    results = {}
    for settled in (True, False):
        eng = make()
        before = counter("serving/settles")
        rids = _run_until_owed(eng, vocab, settled)
        eng.release_request(rids[1])                       # the cancel
        after_cancel = free_counts(eng)
        for expired in (False, True):    # a step on, the deadline passes
            eng.step()
            if settled:
                eng.settle()
            if not expired:
                eng._requests[rids[2]].deadline = Deadline(0.0)
        assert rids[2] not in eng._requests
        eng.settle()
        after_deadline = free_counts(eng)
        outs = _drain(eng, rids)
        results[settled] = (after_cancel, after_deadline, outs[0],
                            free_counts(eng))
        if not settled:
            assert moved("serving/settles", before)["why=release"] == 2
    for a, b in zip(results[True], results[False]):
        np.testing.assert_array_equal(a, b)
    assert results[False][3] == free_counts(make())


def check_forced_preemption(make_small, vocab):
    """`make_small()` builds an engine whose pool cannot hold three rows
    to their ends: the scheduler evicts, and before it does the step in
    flight is read back (the snapshot carries tokens and key as the host
    has them)."""
    results = {}
    for settled in (True, False):
        eng = make_small()
        before = counter("serving/settles")
        ps = prompts(vocab)
        rids = [eng.add_request(ps[i], params(i, 14)) for i in range(3)]
        preempted = 0
        while eng.has_unfinished():
            eng.step()
            if settled:
                eng.settle()
            preempted = max(preempted, sum(
                eng._requests[r].num_preemptions for r in rids))
        assert preempted > 0
        if not settled:
            assert moved("serving/settles", before).get("why=preempt", 0) > 0
        results[settled] = ([eng.request_output(r) for r in rids],
                            free_counts(eng))
        for r in rids:
            eng.release_request(r)
        assert free_counts(eng) == free_counts(make_small())
    for a, b in zip(results[True][0], results[False][0]):
        np.testing.assert_array_equal(a, b)
    assert results[True][1] == results[False][1]
