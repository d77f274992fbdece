"""ISSUE 15 — automatic prefix caching + speculative decoding.

Fast tier (subprocess-free): the chained-key scheme, prefix-index
adoption / LRU-park / reclaim-last semantics, refcount stability under
fork+evict+swap, int8 scale plumbing through adopt/CoW/swap, and the
n-gram proposer — all at cache/module level, no engine compile.

Slow tier: engine A/B doubles — spec-on greedy token-identical to dense
`generate()`, fixed-seed sampling preserved (documented scope: sampling
rows carry no drafts), prefix-hit == cold-start token-identical,
`serving/compiles` + `jit/recompiles{fn=serving:*}` FLAT across
hit/miss, spec rounds and batch-composition crossings, and
deadline-expired/aborted requests decref — never free — shared prefix
blocks.  (The fast tier covers the same engine surface through the ONE
serve_smoke subprocess in test_serving.py.)
"""
import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import GPTForCausalLM, gpt_test_config
from paddle_tpu.serving import (BlockKVCache, EngineConfig, LLMEngine,
                                SamplingParams, prefix_block_keys,
                                propose_ngram)

BS = 4   # block size for the cache-level tests


def _cache(num_blocks=8, **kw):
    return BlockKVCache(num_layers=1, num_blocks=num_blocks, block_size=BS,
                        num_heads=2, head_dim=4, **kw)


class TestPrefixKeys:
    def test_chained_keys_identify_block_aligned_prefixes(self):
        toks = list(range(100, 117))            # 4 full blocks + 1 tail
        keys = prefix_block_keys(toks, BS)
        assert len(keys) == 4
        # same content -> same chain, prefix-wise
        assert prefix_block_keys(toks[:8], BS) == keys[:2]
        # divergence in block 2 changes every key from there on
        other = prefix_block_keys(toks[:8] + [1, 2, 3, 4] + toks[12:], BS)
        assert other[:2] == keys[:2]
        assert other[2] != keys[2] and other[3] != keys[3]
        # a SHIFTED block with identical tokens keys differently (the
        # chain encodes the whole prefix, not the block content alone)
        shifted = prefix_block_keys(toks[4:12], BS)
        assert shifted[0] != keys[1]

    def test_deterministic_across_calls(self):
        toks = [7, 1, 7, 1, 7, 1, 7, 1]
        assert prefix_block_keys(toks, BS) == prefix_block_keys(toks, BS)


class TestPrefixIndex:
    def test_register_match_adopt_refcounts(self):
        c = _cache()
        toks = list(range(17))
        keys = prefix_block_keys(toks, BS)
        c.allocate("a", 17)
        c.register_prefix("a", keys, 17)
        assert c.match_prefix(keys) == 4
        # adoption bumps the SHARED refcount; nothing moves
        free_before = len(c._free)
        assert c.adopt_prefix("b", keys, 3) == 12
        assert len(c._free) == free_before
        for idx in c._tables["b"]:
            assert c._blocks[idx].ref == 2
        assert c._tables["b"] == c._tables["a"][:3]
        assert c.prefix_hits == 1 and c.prefix_hit_tokens == 12

    def test_partial_chain_match_stops_at_first_miss(self):
        c = _cache()
        toks = list(range(16))
        keys = prefix_block_keys(toks, BS)
        c.allocate("a", 16)
        c.register_prefix("a", keys, 8)     # only 2 blocks computed yet
        divergent = prefix_block_keys(toks[:4] + [99, 98, 97, 96]
                                      + toks[8:], BS)
        assert c.match_prefix(keys) == 2
        assert c.match_prefix(divergent) == 1
        assert c.match_prefix(keys, max_blocks=1) == 1

    def test_park_on_release_and_reclaim_last(self):
        c = _cache(num_blocks=6)
        toks = list(range(8))
        keys = prefix_block_keys(toks, BS)
        c.allocate("a", 8)
        c.register_prefix("a", keys, 8)
        c.free("a")
        # indexed blocks PARK instead of joining the free list...
        assert c.num_parked_blocks == 2
        assert c.blocks_in_use == 2          # parked != free capacity
        assert c.num_free_blocks == 6        # ...but stay allocatable
        # the free list drains FIRST; parked blocks are reclaimed last
        c.allocate("x", 4 * BS)              # takes the 4 free blocks
        assert c.prefix_evictions == 0
        assert c.match_prefix(keys) == 2     # cache intact
        c.allocate("y", BS)                  # must reclaim one parked
        assert c.prefix_evictions == 1
        assert c.match_prefix(keys) <= 1     # LRU-oldest entry dropped

    def test_lru_order_is_recency(self):
        c = _cache(num_blocks=4)
        k1 = prefix_block_keys([1] * BS, BS)
        k2 = prefix_block_keys([2] * BS, BS)
        c.allocate("a", BS)
        c.register_prefix("a", k1, BS)
        c.free("a")
        c.allocate("b", BS)
        c.register_prefix("b", k2, BS)
        c.free("b")
        # touch k1 (a match refreshes recency) -> k2 becomes LRU-oldest
        assert c.match_prefix(k1) == 1
        c.allocate("x", 2 * BS)              # drains the free list
        c.allocate("y", BS)                  # reclaims ONE parked: k2
        assert c.match_prefix(k1) == 1
        assert c.match_prefix(k2) == 0

    def test_adopt_revives_parked_block(self):
        c = _cache()
        keys = prefix_block_keys(list(range(8)), BS)
        c.allocate("a", 8)
        c.register_prefix("a", keys, 8)
        c.free("a")
        assert c.num_parked_blocks == 2
        c.adopt_prefix("b", keys, 2)
        assert c.num_parked_blocks == 0
        for idx in c._tables["b"]:
            assert c._blocks[idx].ref == 1
        c.free("b")
        assert c.num_parked_blocks == 2      # parks again

    def test_adoptable_free_blocks_subtracts_parked_hits(self):
        c = _cache(num_blocks=2)
        keys = prefix_block_keys(list(range(8)), BS)
        c.allocate("a", 8)
        c.register_prefix("a", keys, 8)
        c.free("a")
        # both blocks parked: naive capacity says 2 free, but adopting
        # both leaves NOTHING reclaimable for growth
        assert c.num_free_blocks == 2
        assert c.adoptable_free_blocks(keys, 2) == 0
        assert c.adoptable_free_blocks(keys, 1) == 1

    def test_refcount_stability_under_fork_evict_swap(self):
        c = _cache(num_blocks=10)
        toks = list(range(12))
        keys = prefix_block_keys(toks, BS)
        c.allocate("a", 12)
        c.register_prefix("a", keys, 12)
        c.adopt_prefix("b", keys, 2)         # b shares blocks 0,1
        c.grow_to("b", 12)                   # private tail
        c.fork("b", "b2")                    # fork bumps every ref
        shared = c._tables["a"][:2]
        assert [c._blocks[i].ref for i in shared] == [3, 3]   # a, b, b2
        # evict b: snapshot + decref (NEVER a hard free of shared blocks)
        saved = c.swap_out("b")
        assert [c._blocks[i].ref for i in shared] == [2, 2]
        c.swap_in("b", saved)
        # restored into PRIVATE fresh blocks; shared refs unchanged
        assert [c._blocks[i].ref for i in shared] == [2, 2]
        assert c._tables["b"][0] not in shared
        for name in ("a", "b", "b2"):
            c.free(name)
        # a's indexed blocks park; everything else back on the free list
        assert c.num_parked_blocks == 3
        assert c.blocks_in_use == 3
        assert c.match_prefix(keys) == 3

    def test_register_is_first_writer_wins(self):
        c = _cache()
        keys = prefix_block_keys(list(range(8)), BS)
        c.allocate("a", 8)
        c.register_prefix("a", keys, 8)
        orig = list(c._tables["a"])
        c.allocate("b", 8)
        c.register_prefix("b", keys, 8)      # duplicate content
        assert [c._prefix_index[k] for k in keys] == orig


class TestPrefixInt8Scales:
    def _fill(self, c, idx, seed):
        rng = np.random.RandomState(seed)
        codes = rng.randint(-127, 128, c.k_blocks[0][idx].shape).astype(
            np.int8)
        scales = rng.rand(c.num_heads).astype(np.float32)
        c.k_blocks[0] = c.k_blocks[0].at[idx].set(jnp.asarray(codes))
        c.v_blocks[0] = c.v_blocks[0].at[idx].set(jnp.asarray(codes))
        c.k_scales[0] = c.k_scales[0].at[idx].set(jnp.asarray(scales))
        c.v_scales[0] = c.v_scales[0].at[idx].set(jnp.asarray(scales))
        return codes, scales

    def test_scales_ride_adopt_cow_and_swap_bitwise(self):
        c = _cache(kv_quant="int8")
        keys = prefix_block_keys(list(range(8)), BS)
        c.allocate("a", 8)
        codes0, scales0 = self._fill(c, c._tables["a"][0], 0)
        codes1, scales1 = self._fill(c, c._tables["a"][1], 1)
        c.register_prefix("a", keys, 8)
        c.free("a")
        # adoption shares the SAME physical blocks: codes+scales exact
        c.adopt_prefix("b", keys, 2)
        i0, i1 = c._tables["b"]
        np.testing.assert_array_equal(np.asarray(c.k_blocks[0][i0]), codes0)
        np.testing.assert_array_equal(np.asarray(c.k_scales[0][i0]),
                                      scales0)
        # swap round-trip restores codes AND scales bit-exactly into
        # fresh private blocks
        saved = c.swap_out("b")
        c.adopt_prefix("b2", keys, 2)        # keep the originals parked-free
        c.swap_in("b", saved)
        j0, j1 = c._tables["b"]
        np.testing.assert_array_equal(np.asarray(c.k_blocks[0][j0]), codes0)
        np.testing.assert_array_equal(np.asarray(c.k_scales[0][j0]),
                                      scales0)
        np.testing.assert_array_equal(np.asarray(c.v_scales[0][j1]),
                                      scales1)
        # CoW of a shared block copies scales with the codes
        c.grow_to("b", 8)                    # covers both blocks
        c._cow_last_block("b")
        d1 = c._tables["b"][-1]
        assert d1 != j1
        np.testing.assert_array_equal(np.asarray(c.k_blocks[0][d1]), codes1)
        np.testing.assert_array_equal(np.asarray(c.k_scales[0][d1]),
                                      scales1)

    def test_reclaimed_parked_block_gets_zeroed_scales(self):
        c = _cache(num_blocks=2, kv_quant="int8")
        keys = prefix_block_keys(list(range(8)), BS)
        c.allocate("a", 8)
        self._fill(c, c._tables["a"][0], 0)
        c.register_prefix("a", keys, 8)
        c.free("a")
        c.allocate("x", 8)                   # reclaims both parked blocks
        assert c.prefix_evictions == 2
        assert float(jnp.max(jnp.abs(c.k_scales[0]))) == 0.0


class TestNgramProposer:
    def test_repeating_pattern_is_predicted(self):
        ctx = [1, 2, 3, 4] * 4
        # suffix [2,3,4] recurs; the cycle continues with [1,2,3]
        assert propose_ngram(ctx, 3) == [1, 2, 3]

    def test_longest_ngram_wins_over_shorter_ambiguity(self):
        # suffix [5, 1]: 3-gram [9, 5, 1] matches earlier -> follow 7;
        # a 1-gram match of [1] alone would propose 9
        ctx = [9, 5, 1, 7, 3, 1, 9, 5, 1]
        assert propose_ngram(ctx, 2, ngram_max=3)[:1] == [7]

    def test_most_recent_occurrence_preferred(self):
        ctx = [1, 2, 8, 1, 2, 9, 1, 2]
        assert propose_ngram(ctx, 1, ngram_max=2) == [9]

    def test_no_match_returns_empty(self):
        assert propose_ngram([1, 2, 3, 4, 5], 3) == []
        assert propose_ngram([1], 3) == []
        assert propose_ngram([1, 2, 3], 0) == []

    def test_window_bounds_the_scan(self):
        ctx = [5, 6] + [0] * 50 + [5, 6]
        assert propose_ngram(ctx, 1, ngram_max=2, window=10) == []
        assert propose_ngram(ctx, 1, ngram_max=2, window=100) == [0]

    def test_overlapping_cycle_continuation(self):
        # the draft window ends at the context frontier (no wrap-around
        # extrapolation): a short cycle still drafts what exists
        ctx = [1, 2, 1, 2, 1]
        assert propose_ngram(ctx, 4) == [2, 1]


class TestSpecReservation:
    def test_decode_reserve_clamps_like_the_proposer(self):
        """The scheduler's draft-extent reservation mirrors the engine
        proposer's clamp: sampling rows and rows within one token of
        max_new_tokens / max_model_len reserve NOTHING extra — a block
        nobody will write must never evict a neighbour."""
        from paddle_tpu.serving import Request, Scheduler

        s = Scheduler(_cache(num_blocks=16), spec_tokens=3,
                      max_model_len=20)
        r = Request("r", list(range(8)), SamplingParams(max_new_tokens=5))
        r.output_ids = [1]                     # total_len 9
        assert s._decode_reserve_len(r) == 12  # full k=3 extent
        r.output_ids = [1, 2, 3, 4]            # one emit left
        assert s._decode_reserve_len(r) == 12  # == total_len, extra 0
        r2 = Request("r2", list(range(8)),
                     SamplingParams(max_new_tokens=5, do_sample=True))
        r2.output_ids = [1]
        assert s._decode_reserve_len(r2) == 9  # sampling: never drafts
        r3 = Request("r3", list(range(16)),
                     SamplingParams(max_new_tokens=8))
        r3.output_ids = [1, 2]                 # total_len 18, cap 20
        assert s._decode_reserve_len(r3) == 20


# ---------------------------------------------------------------------------
# slow tier: engine A/B doubles
# ---------------------------------------------------------------------------

NEW = 6


@pytest.fixture(scope="module")
def model():
    cfg = gpt_test_config(stacked_blocks=True, sequence_parallel=False)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


def _dense(model, prompt, **kw):
    out = model.generate(Tensor(jnp.asarray(np.asarray(prompt)[None])),
                         max_new_tokens=NEW, **kw)
    return np.asarray(out._data)[0]


@pytest.fixture(scope="module")
def shared_prompts(model):
    rng = np.random.RandomState(0)
    V = model.cfg.vocab_size
    shared = rng.randint(0, V, (32,)).astype(np.int32)
    tails = [rng.randint(0, V, (t,)).astype(np.int32) for t in (5, 9, 5)]
    return [np.concatenate([shared, t]) for t in tails]


@pytest.mark.slow
class TestSpecEngineParity:
    def test_spec_greedy_token_identical_to_dense(self, model):
        rng = np.random.RandomState(1)
        V = model.cfg.vocab_size
        prompts = [rng.randint(0, V, (n,)).astype(np.int32)
                   for n in (4, 7, 6)]
        dense = [_dense(model, p) for p in prompts]
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4,
                                            speculative_tokens=3))
        outs = eng.generate(prompts, SamplingParams(max_new_tokens=NEW))
        for i, (d, e) in enumerate(zip(dense, outs)):
            np.testing.assert_array_equal(d, e, err_msg=f"request {i}")
        assert eng.cache.blocks_in_use == 0   # spec reservations rolled back
        assert eng._spec_proposed_total >= eng._spec_accepted_total

    def test_spec_seeded_sampling_stream_preserved(self, model):
        """Documented scope: sampling rows carry no drafts, so their
        per-request PRNG stream is exactly the sequential one."""
        rng = np.random.RandomState(2)
        V = model.cfg.vocab_size
        prompts = [rng.randint(0, V, (n,)).astype(np.int32) for n in (4, 6)]
        kw = dict(do_sample=True, temperature=0.8, top_k=20, top_p=0.9)
        dense = [_dense(model, p, **dict(kw, seed=11 + i))
                 for i, p in enumerate(prompts)]
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4,
                                            speculative_tokens=3))
        sps = [SamplingParams(max_new_tokens=NEW, seed=11 + i, **kw)
               for i in range(len(prompts))]
        outs = eng.generate(prompts, sps)
        for i, (d, e) in enumerate(zip(dense, outs)):
            np.testing.assert_array_equal(d, e, err_msg=f"request {i}")

    def test_spec_eos_early_stop_matches_dense(self, model):
        rng = np.random.RandomState(3)
        prompt = rng.randint(0, model.cfg.vocab_size, (4,)).astype(np.int32)
        probe = _dense(model, prompt)
        eos = int(probe[len(prompt) + 1])
        dense = _dense(model, prompt, eos_token_id=eos)
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4,
                                            speculative_tokens=3))
        [out] = eng.generate(
            [prompt], SamplingParams(max_new_tokens=NEW, eos_token_id=eos))
        np.testing.assert_array_equal(dense, out)

    def test_compiles_flat_across_spec_rounds_and_crossings(self, model):
        monitor.enable(True)
        try:
            eng = LLMEngine(model, EngineConfig(
                block_size=16, max_num_seqs=8, speculative_tokens=3))
            rng = np.random.RandomState(4)
            V = model.cfg.vocab_size
            mk = lambda ns: [rng.randint(0, V, (n,)).astype(np.int32)
                             for n in ns]
            sp = SamplingParams(max_new_tokens=4)
            eng.generate(mk((4, 6, 4)), sp)        # warm: 3 rows
            snap = monitor.snapshot()
            compiles = sum(snap["serving/compiles"].values())
            causes = sum(v for k, v in sorted(
                (snap.get("jit/recompile_cause") or {}).items())
                if "serving:" in k)
            eng.generate(mk((4, 6, 4, 6, 4)), sp)  # 3 -> 5 crossing
            snap = monitor.snapshot()
            assert sum(snap["serving/compiles"].values()) == compiles
            assert sum(v for k, v in sorted(
                (snap.get("jit/recompile_cause") or {}).items())
                if "serving:" in k) == causes
        finally:
            monitor.refresh()


@pytest.mark.slow
class TestPrefixEngineParity:
    def test_prefix_hit_token_identical_to_cold(self, model, shared_prompts):
        dense = [_dense(model, p) for p in shared_prompts]
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4,
                                            enable_prefix_caching=True))
        sp = SamplingParams(max_new_tokens=NEW)
        cold = eng.generate([shared_prompts[0]], sp)
        assert eng.cache.prefix_hits == 0
        np.testing.assert_array_equal(dense[0], cold[0])
        hot = eng.generate(shared_prompts, sp)     # all three adopt
        for i, (d, e) in enumerate(zip(dense, hot)):
            np.testing.assert_array_equal(d, e, err_msg=f"request {i}")
        assert eng.cache.prefix_hits == 3
        assert eng.cache.prefix_hit_tokens == 3 * 32

    def test_prefix_plus_spec_token_identical(self, model, shared_prompts):
        dense = [_dense(model, p) for p in shared_prompts]
        eng = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=4, enable_prefix_caching=True,
            speculative_tokens=3))
        sp = SamplingParams(max_new_tokens=NEW)
        eng.generate([shared_prompts[0]], sp)
        hot = eng.generate(shared_prompts, sp)
        for i, (d, e) in enumerate(zip(dense, hot)):
            np.testing.assert_array_equal(d, e, err_msg=f"request {i}")

    def test_utilization_counts_parked_blocks(self, model, shared_prompts):
        monitor.enable(True)
        try:
            eng = LLMEngine(model, EngineConfig(
                block_size=16, max_num_seqs=4, enable_prefix_caching=True))
            eng.generate([shared_prompts[0]],
                         SamplingParams(max_new_tokens=2))
            # finished request parked its prompt blocks: they hold live
            # reusable bytes, NOT free capacity
            assert eng.cache.num_parked_blocks == 2
            assert eng.cache.blocks_in_use == 2
            eng.step()                        # idle step refreshes gauges
            assert monitor.gauge("serving/blocks_in_use").value == 2
            assert monitor.gauge("serving/block_utilization").value > 0
        finally:
            monitor.refresh()

    def test_abort_and_deadline_decref_never_free_shared_blocks(
            self, model, shared_prompts):
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4,
                                            enable_prefix_caching=True))
        sp = SamplingParams(max_new_tokens=NEW)
        dense = [_dense(model, p) for p in shared_prompts]
        eng.generate([shared_prompts[0]], sp)
        # two adopters of the same parked prefix
        ra = eng.add_request(shared_prompts[0], sp)
        rb = eng.add_request(shared_prompts[1], sp)
        while not (eng._requests[ra].prefill_done
                   and eng._requests[rb].prefill_done):
            eng.step()
        shared_ids = eng.cache._tables[ra][:2]
        assert eng.cache._tables[rb][:2] == shared_ids
        refs = [eng.cache._blocks[i].ref for i in shared_ids]
        assert refs == [2, 2]
        # abort ra mid-flight: DECREF — rb keeps the blocks and finishes
        # with the cold-run tokens
        eng.release_request(ra)
        assert [eng.cache._blocks[i].ref for i in shared_ids] == [1, 1]
        while eng.has_unfinished():
            eng.step()
        np.testing.assert_array_equal(dense[1], eng.request_output(rb))
        eng.release_request(rb)
        # blocks parked again (ref 0, still indexed), never hard-freed
        assert all(eng.cache._blocks[i].ref == 0 for i in shared_ids)
        assert eng.cache.num_parked_blocks >= 2
        # deadline expiry goes through the same release path
        monitor.enable(True)
        try:
            rc = eng.add_request(
                shared_prompts[2],
                SamplingParams(max_new_tokens=NEW, deadline_s=1e-6))
            eng.step()          # prefill (adopts)
            import time as _t
            _t.sleep(0.01)
            eng.step()          # expiry sweep aborts rc
            assert rc not in eng._requests
            assert monitor.snapshot().get("serving/deadline_expired", 0) >= 1
        finally:
            monitor.refresh()
        # the pool survived every abort with the index intact
        assert eng.cache.blocks_in_use == eng.cache.num_parked_blocks

    def test_chunk_budget_counts_only_uncached_tokens(self, model):
        """The small-fix satellite: a prefix-hit request's prefill
        chunking budgets its UNCACHED tail, not the whole prompt — a
        48-token hot prompt with 32 cached tokens admits its 16-token
        tail in ONE budget-sized chunk."""
        monitor.enable(True)
        try:
            rng = np.random.RandomState(12)
            V = model.cfg.vocab_size
            shared = rng.randint(0, V, (32,)).astype(np.int32)
            mk = lambda: np.concatenate(
                [shared, rng.randint(0, V, (16,)).astype(np.int32)])
            eng = LLMEngine(model, EngineConfig(
                block_size=16, max_num_seqs=2, enable_prefix_caching=True,
                max_num_batched_tokens=16))
            sp = SamplingParams(max_new_tokens=2)
            eng.generate([mk()], sp)                  # cold: 3 chunks
            pre = monitor.snapshot()["serving/prefill_tokens"]
            eng.generate([mk()], sp)                  # hot: 1 chunk
            assert eng.cache.prefix_hits == 1
            delta = monitor.snapshot()["serving/prefill_tokens"] - pre
            assert delta == 16, delta
        finally:
            monitor.refresh()

    def test_compiles_flat_across_hit_miss(self, model, shared_prompts):
        monitor.enable(True)
        try:
            eng = LLMEngine(model, EngineConfig(
                block_size=16, max_num_seqs=4, enable_prefix_caching=True))
            sp = SamplingParams(max_new_tokens=4)
            rng = np.random.RandomState(9)
            V = model.cfg.vocab_size
            eng.generate([shared_prompts[0]], sp)        # cold: compiles
            eng.generate(shared_prompts, sp)             # hot: compiles
            #                                              ragged(1, tail)
            snap = monitor.snapshot()
            compiles = sum(snap["serving/compiles"].values())
            # round 2: same shapes, mixed hit + miss — zero fresh programs
            miss = rng.randint(0, V, (37,)).astype(np.int32)
            hit = np.concatenate([shared_prompts[0][:32],
                                  rng.randint(0, V, (5,)).astype(np.int32)])
            eng.generate([hit, miss], sp)
            snap = monitor.snapshot()
            assert sum(snap["serving/compiles"].values()) == compiles
        finally:
            monitor.refresh()


@pytest.mark.slow
class TestInt8PrefixSpec:
    def test_int8_prefix_hit_matches_int8_cold(self, model, shared_prompts):
        """int8-KV: hit-vs-cold compared WITHIN the quantized engine —
        adopted blocks carry the same codes+scales the cold run wrote,
        so outputs are identical (the fp-vs-int8 gap itself is the
        documented PR-4 tolerance, pinned in test_lowbit)."""
        sp = SamplingParams(max_new_tokens=NEW)
        cold_eng = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=4, kv_cache_dtype="int8"))
        cold = cold_eng.generate(shared_prompts, sp)
        eng = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=4, kv_cache_dtype="int8",
            enable_prefix_caching=True))
        eng.generate([shared_prompts[0]], sp)
        hot = eng.generate(shared_prompts, sp)
        assert eng.cache.prefix_hits == 3
        for i, (d, e) in enumerate(zip(cold, hot)):
            np.testing.assert_array_equal(d, e, err_msg=f"request {i}")

    def test_int8_spec_greedy_tolerance(self, model):
        """int8-KV + spec: rejected draft writes can grow a block's
        monotonic scale, so parity vs the non-spec int8 engine is the
        documented agreement tolerance, not bitwise."""
        rng = np.random.RandomState(6)
        V = model.cfg.vocab_size
        prompts = [rng.randint(0, V, (n,)).astype(np.int32) for n in (4, 6)]
        sp = SamplingParams(max_new_tokens=NEW)
        ref_eng = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=4, kv_cache_dtype="int8"))
        ref = ref_eng.generate(prompts, sp)
        eng = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=4, kv_cache_dtype="int8",
            speculative_tokens=3))
        outs = eng.generate(prompts, sp)
        agree = np.mean([float((r[len(p):] == o[len(p):]).mean())
                         for r, o, p in zip(ref, outs, prompts)])
        assert agree >= 0.9, agree


# ---------------------------------------------------------------------------
# ISSUE 31: the speculative step crosses to the device a fixed number of times
# ---------------------------------------------------------------------------

def test_spec_step_crosses_the_same_number_of_times_at_any_batch(model):
    """A verify step uploads twice (verify inputs, sampler inputs) and
    reads tokens, keys and the greedy run back in one call, at one live
    row and at `max_num_seqs`, whatever each row drafts (the drafts here
    are made up, of unequal length, one row with none: the verify program
    rejects them, which changes no count)."""
    def calls():
        snap = monitor.snapshot().get("serving/device_calls", {})
        return np.array([snap.get("dir=h2d", 0), snap.get("dir=d2h", 0)],
                        int)

    monitor.enable(True)
    try:
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4,
                                            speculative_tokens=3))
        spec_rows = []
        body = eng._decode_body_spec

        def watched(rows, drafts):
            spec_rows.append((len(rows), sorted(len(d) for d in drafts)))
            return body(rows, drafts)

        want = {0: 3}                  # request id -> drafts it proposes

        def propose(req):
            budget = min(want[req.req_id],
                         req.params.max_new_tokens - len(req.output_ids) - 1,
                         eng.max_model_len - req.total_len)
            return [1] * max(budget, 0)

        eng._decode_body_spec = watched
        eng._propose = propose
        rng = np.random.RandomState(2)
        sp = SamplingParams(max_new_tokens=10)
        ids = [eng.add_request(rng.randint(0, model.cfg.vocab_size, (6,)),
                               sp)]
        eng.step()                                     # its prefill
        before = calls()
        eng.step()
        assert tuple(calls() - before) == (2, 1)
        assert spec_rows == [(1, [3])]
        for n in (4, 9, 5):
            ids.append(eng.add_request(
                rng.randint(0, model.cfg.vocab_size, (n,)), sp))
        want.update({0: 0, 1: 1, 2: 2, 3: 3})
        for _ in range(3):
            eng.step()                                 # their prefills
        before = calls()
        eng.step()
        assert tuple(calls() - before) == (2, 1)
        assert spec_rows[-1] == (4, [0, 1, 2, 3])
        while eng.has_unfinished():
            before = calls()
            eng.step()
            assert tuple(calls() - before) == (2, 1)
        assert eng.cache.blocks_in_use == 0
        for i in ids:
            eng.release_request(i)
    finally:
        monitor.refresh()
