"""paddle_tpu.serving — continuous batching over a paged KV cache.

The bar (ISSUE 2 acceptance): `LLMEngine.generate()` over a mixed-length
batch returns EXACTLY the tokens of independent dense
`GPTModel.generate()` calls — greedy and fixed-seed sampling — while the
paged pool peaks below the dense `[B, S_max]` equivalent; preempted
requests resume bit-identically; the block allocator never double-books;
the `serving/*` metrics land in the monitor snapshot.
"""
import os
import pathlib
import subprocess
import sys

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models import GPTForCausalLM, gpt_test_config
from paddle_tpu.serving import (BlockAllocatorError, BlockKVCache,
                                EngineConfig, LLMEngine, SamplingParams)

import _step_in_flight as sif

NEW = 5
LENS = [3, 5, 7, 3, 5, 7, 4, 4]        # 8 prompts, 4 distinct lengths


@pytest.fixture(scope="module")
def model():
    cfg = gpt_test_config(stacked_blocks=True, sequence_parallel=False)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def prompts(model):
    rng = np.random.RandomState(0)
    return [rng.randint(0, model.cfg.vocab_size, (n,)).astype(np.int32)
            for n in LENS]


@pytest.fixture(scope="module")
def engine(model):
    # ONE engine for the parity tests: its jitted step programs are cached
    # per bucket, which is exactly the serving deployment shape
    return LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=8))


def _dense_solo(model, prompt, **kw):
    out = model.generate(Tensor(jnp.asarray(prompt[None])),
                         max_new_tokens=NEW, **kw)
    return np.asarray(out._data)[0]


def _dense_all(model, prompts, kw_fn):
    """Solo dense runs grouped by (length, sampling key) so the dense
    path's single-slot executable cache is reused."""
    order = sorted(range(len(prompts)), key=lambda i: len(prompts[i]))
    outs = [None] * len(prompts)
    for i in order:
        outs[i] = _dense_solo(model, prompts[i], **kw_fn(i))
    return outs


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("nh", [2, 4])
def test_block_splits_the_qkv_product_in_the_column_order(nh, dtype):
    """The block splits the `[B, S, 3H]` product's RESULT (so that no
    program re-lays the stacked weight: tests/test_tpu_aot_layout.py); the
    heads it hands to attention are, bit for bit, those of the
    `reshape(B, S, 3, nh, hd)[:, :, i]` the weights' columns were laid
    out for."""
    from paddle_tpu.models.gpt import _split_heads

    mb, s, hd = 2, 5, 8
    H = nh * hd
    rng = np.random.RandomState(nh)
    hn, w, b = (jnp.asarray(rng.standard_normal(shape), dtype)
                for shape in [(mb, s, H), (H, 3 * H), (3 * H,)])
    qkv = hn @ w + b
    want = qkv.reshape(mb, s, 3, nh, hd)
    got = _split_heads(qkv, nh, hd)
    assert len(got) == 3
    for i, t in enumerate(got):
        assert t.shape == (mb, s, nh, hd) and t.dtype == qkv.dtype
        np.testing.assert_array_equal(np.asarray(t, np.float32),
                                      np.asarray(want[:, :, i], np.float32))


class TestDenseParity:
    def test_greedy_mixed_length_batch(self, model, prompts, engine):
        dense = _dense_all(model, prompts, lambda i: {})
        outs = engine.generate(prompts, SamplingParams(max_new_tokens=NEW))
        for i, (d, e) in enumerate(zip(dense, outs)):
            np.testing.assert_array_equal(d, e, err_msg=f"request {i}")
        # every finished request freed its blocks...
        assert engine.cache.blocks_in_use == 0
        # ...and the paged peak stayed below the dense [B, S_max] pool:
        # dense allocates ceil(round128(P+NEW)/block) blocks per request
        dense_blocks = sum(
            -(-(-(-(len(p) + NEW) // 128) * 128) // 16) for p in prompts)
        assert engine.cache.peak_blocks_in_use < dense_blocks

    def test_seeded_sampling_mixed_length_batch(self, model, prompts,
                                                engine):
        kw = dict(do_sample=True, temperature=0.8, top_k=20, top_p=0.9)
        dense = _dense_all(model, prompts,
                           lambda i: dict(kw, seed=7 + i))
        sps = [SamplingParams(max_new_tokens=NEW, do_sample=True,
                              temperature=0.8, top_k=20, top_p=0.9,
                              seed=7 + i) for i in range(len(prompts))]
        outs = engine.generate(prompts, sps)
        for i, (d, e) in enumerate(zip(dense, outs)):
            np.testing.assert_array_equal(d, e, err_msg=f"request {i}")

    def test_staggered_arrivals_match_solo(self, model, prompts, engine):
        """Continuous batching proper: requests joining MID-FLIGHT still
        produce their solo outputs (the batch composition around a row
        must not leak into it)."""
        dense = _dense_all(model, prompts, lambda i: {})
        first = [engine.add_request(p, SamplingParams(max_new_tokens=NEW))
                 for p in prompts[:4]]
        for _ in range(3):
            engine.step()
        late = [engine.add_request(p, SamplingParams(max_new_tokens=NEW))
                for p in prompts[4:]]
        while engine.has_unfinished():
            engine.step()
        for i, rid in enumerate(first + late):
            np.testing.assert_array_equal(
                dense[i], engine.request_output(rid),
                err_msg=f"request {i}")

    def test_eos_early_stop_matches_dense(self, model, engine):
        rng = np.random.RandomState(3)
        prompt = rng.randint(0, model.cfg.vocab_size, (4,)).astype(np.int32)
        probe = _dense_solo(model, prompt)
        eos = int(probe[len(prompt) + 1])     # the 2nd greedy token
        dense = _dense_solo(model, prompt, eos_token_id=eos)
        [out] = engine.generate(
            [prompt], SamplingParams(max_new_tokens=NEW, eos_token_id=eos))
        np.testing.assert_array_equal(dense, out)
        assert out[-1] == eos and len(out) < len(prompt) + NEW


class TestPreemption:
    def test_preempted_requests_resume_identical(self, model):
        """A pool too small for both requests forces eviction; the host
        swap restores KV bit-exactly, so outputs equal solo dense runs
        (greedy AND a seeded-sampling row exercising PRNG-key state)."""
        rng = np.random.RandomState(1)
        pa = rng.randint(0, model.cfg.vocab_size, (14,)).astype(np.int32)
        pb = rng.randint(0, model.cfg.vocab_size, (15,)).astype(np.int32)
        da = _dense_solo(model, pa)
        db = _dense_solo(model, pb, do_sample=True, temperature=0.9,
                         top_k=16, seed=11)
        # 14+NEW and 15+NEW tokens → 2 blocks each; 3 physical blocks
        # cannot hold both past the 16-token boundary
        eng = LLMEngine(model, EngineConfig(block_size=16, num_blocks=3,
                                            max_num_seqs=2))
        outs = eng.generate(
            [pa, pb],
            [SamplingParams(max_new_tokens=NEW),
             SamplingParams(max_new_tokens=NEW, do_sample=True,
                            temperature=0.9, top_k=16, seed=11)])
        assert monitor  # keep import referenced even when disabled
        np.testing.assert_array_equal(da, outs[0])
        np.testing.assert_array_equal(db, outs[1])
        assert eng._m_preempt.value >= 1, "pool was sized to force eviction"


class TestSchedulerEdges:
    def test_eviction_churn_never_decodes_a_preempted_row(self, model):
        """A later decode row's block reservation may evict an earlier
        row ALREADY in the batch; the preempted row must be dropped from
        the step (previously: KeyError on its freed block table) and
        outputs still match dense solos through the churn."""
        rng = np.random.RandomState(7)
        pa = rng.randint(0, model.cfg.vocab_size, (2,)).astype(np.int32)
        pb = rng.randint(0, model.cfg.vocab_size, (4,)).astype(np.int32)
        da = _dense_solo(model, pa)
        db = _dense_solo(model, pb)
        # pool of 3 (B alone needs all 3 at its final length) → constant
        # eviction churn while both are live
        eng = LLMEngine(model, EngineConfig(block_size=4, num_blocks=3,
                                            max_num_seqs=2))
        outs = eng.generate([pa, pb], SamplingParams(max_new_tokens=NEW))
        np.testing.assert_array_equal(da, outs[0])
        np.testing.assert_array_equal(db, outs[1])

    def test_request_larger_than_pool_raises_not_hangs(self, model):
        """A request whose KV footprint exceeds the whole pool must raise
        'KV cache too small' (previously: perpetual self-evict/swap-in
        livelock under chunked prefill)."""
        rng = np.random.RandomState(8)
        prompt = rng.randint(0, model.cfg.vocab_size, (16,)).astype(np.int32)
        eng = LLMEngine(model, EngineConfig(
            block_size=4, num_blocks=2, max_num_seqs=1,
            max_num_batched_tokens=4))
        with pytest.raises(RuntimeError, match="KV cache too small"):
            eng.generate([prompt], SamplingParams(max_new_tokens=2))

    def test_generate_releases_requests_on_error(self, model):
        """A mid-loop 'KV cache too small' must not leak the other
        admitted requests' blocks or poison the next generate() call."""
        rng = np.random.RandomState(10)
        small = rng.randint(0, model.cfg.vocab_size, (3,)).astype(np.int32)
        big = rng.randint(0, model.cfg.vocab_size, (16,)).astype(np.int32)
        eng = LLMEngine(model, EngineConfig(
            block_size=4, num_blocks=2, max_num_seqs=2,
            max_num_batched_tokens=4))
        with pytest.raises(RuntimeError, match="KV cache too small"):
            eng.generate([small, big], SamplingParams(max_new_tokens=2))
        assert not eng._requests
        assert eng.cache.blocks_in_use == 0
        assert not eng.has_unfinished()
        # the engine is still serviceable
        [out] = eng.generate([small], SamplingParams(max_new_tokens=2))
        d = _dense_solo(model, small)[:5]
        np.testing.assert_array_equal(d, out)

    def test_max_new_tokens_zero_matches_dense(self, model, engine):
        rng = np.random.RandomState(11)
        prompt = rng.randint(0, model.cfg.vocab_size, (4,)).astype(np.int32)
        from paddle_tpu.core.tensor import Tensor as _T
        import jax.numpy as _jnp

        d = model.generate(_T(_jnp.asarray(prompt[None])), max_new_tokens=0)
        [out] = engine.generate([prompt], SamplingParams(max_new_tokens=0))
        np.testing.assert_array_equal(np.asarray(d._data)[0], out)
        assert len(out) == len(prompt)

    def test_blocked_swap_head_does_not_starve_admissible_child(self):
        """Queue head: an evicted request whose snapshot cannot fit; a
        forked-style child (already holding blocks) behind it; nothing
        running.  The scheduler must admit the child (whose completion
        frees blocks) instead of raising 'KV cache too small'."""
        from paddle_tpu.serving import Request, Scheduler

        cache = BlockKVCache(num_layers=1, num_blocks=3, block_size=4,
                             num_heads=1, head_dim=2)
        sched = Scheduler(cache, max_num_seqs=2)
        r = Request("r", list(range(9)), SamplingParams(max_new_tokens=1))
        r.arrival = 0
        cache.allocate("r", 9)                 # 3 blocks
        r.num_computed = 9
        r.output_ids = [1]
        r.swap = cache.swap_out("r")           # evicted: snapshot 3 blocks
        r.state = Request.PREEMPTED
        sched.waiting.append(r)
        child = Request("c", list(range(6)), SamplingParams(max_new_tokens=1))
        child.arrival = 1
        cache.allocate("c", 4)                 # holds its shared prefix
        child.num_computed = 4
        sched.waiting.append(child)
        # head r needs 3 blocks, free is 2 → blocked; child is admissible
        out = sched.schedule()
        assert out.kind == "prefill" and out.prefill_request is child
        assert sched.waiting[0] is r           # FIFO position kept

    def test_release_request_drops_host_state(self, model):
        rng = np.random.RandomState(9)
        prompt = rng.randint(0, model.cfg.vocab_size, (4,)).astype(np.int32)
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2))
        # generate() releases its own requests
        eng.generate([prompt], SamplingParams(max_new_tokens=2))
        assert not eng._requests
        # aborting an unfinished request frees its blocks too
        rid = eng.add_request(prompt, SamplingParams(max_new_tokens=4))
        eng.step()                        # prefill: blocks now held
        assert eng.cache.blocks_in_use > 0
        eng.release_request(rid)
        assert not eng._requests and eng.cache.blocks_in_use == 0
        assert not eng.has_unfinished()


class TestForkCoW:
    def test_engine_fork_shares_prefix_blocks(self, model):
        rng = np.random.RandomState(2)
        prompt = rng.randint(0, model.cfg.vocab_size, (20,)).astype(np.int32)
        # unforked baseline
        base = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2))
        [solo] = base.generate([prompt], SamplingParams(max_new_tokens=NEW))

        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2))
        parent = eng.add_request(prompt, SamplingParams(max_new_tokens=NEW))
        eng.step()                      # prefill + first token
        child = eng.fork_request(
            parent, SamplingParams(max_new_tokens=NEW, do_sample=True,
                                   temperature=0.7, seed=5))
        # the full prefix block stays SHARED (refcount bump, no copy);
        # the partial last block is privatized at fork because the child
        # re-writes its final inherited position through its own prefill
        assert eng.cache.blocks_in_use == 3
        while eng.has_unfinished():
            eng.step()
        # forking must not perturb the parent's stream
        np.testing.assert_array_equal(solo, eng.request_output(parent))
        child_out = eng.request_output(child)
        assert len(child_out) == 21 + NEW      # prompt+tok0 then NEW more
        # one shared full block + two private partial blocks — strictly
        # below two private copies of everything (4)
        assert eng.cache.peak_blocks_in_use <= 4

    def test_kv_cache_copy_on_fork_unit(self):
        cache = BlockKVCache(num_layers=1, num_blocks=8, block_size=4,
                             num_heads=1, head_dim=2)
        cache.allocate("a", 6)                 # blocks 0..1, 6 tokens
        ka = cache.k_blocks[0].at[:].add(0)    # snapshot
        # paint A's content so copies are observable
        cache.k_blocks[0] = ka.at[cache._tables["a"][0]].set(1.0)
        cache.k_blocks[0] = cache.k_blocks[0].at[
            cache._tables["a"][1]].set(2.0)
        cache.fork("a", "b")
        assert cache.block_table("a") == cache.block_table("b")
        assert cache.blocks_in_use == 2        # shared, no copy yet
        # B appends into the shared PARTIAL last block → CoW
        cache.grow_to("b", 7)
        ta, tb = cache.block_table("a"), cache.block_table("b")
        assert ta[0] == tb[0] and ta[1] != tb[1]
        assert cache.blocks_in_use == 3
        # the copy carried the content
        np.testing.assert_array_equal(
            np.asarray(cache.k_blocks[0][ta[1]]),
            np.asarray(cache.k_blocks[0][tb[1]]))
        # A keeps writing its own block; B's copy is private
        cache.free("a")
        assert cache.blocks_in_use == 2        # b0 (shared) + b's copy
        cache.free("b")
        assert cache.blocks_in_use == 0


class TestAllocator:
    def test_free_list_never_double_allocates(self):
        rng = np.random.RandomState(0)
        cache = BlockKVCache(num_layers=1, num_blocks=16, block_size=4,
                             num_heads=1, head_dim=2)
        live = {}
        for step in range(300):
            op = rng.randint(4)
            if op == 0 and len(live) < 6:
                sid = f"s{step}"
                n = int(rng.randint(1, 13))
                if cache.blocks_needed(n) <= cache.num_free_blocks:
                    cache.allocate(sid, n)
                    live[sid] = n
            elif op == 1 and live:
                sid = rng.choice(sorted(live))
                n = live[sid] + int(rng.randint(1, 5))
                if cache.can_grow_to(sid, n):
                    cache.grow_to(sid, n)
                    live[sid] = n
            elif op == 2 and live:
                sid = rng.choice(sorted(live))
                cache.free(sid)
                del live[sid]
            elif op == 3 and live and len(live) < 6:
                src = rng.choice(sorted(live))
                sid = f"f{step}"
                cache.fork(src, sid)
                live[sid] = live[src]
            # INVARIANT: every live table references distinct slots unless
            # explicitly shared, and free blocks have refcount 0
            held = [b for t in cache._tables.values() for b in t]
            for b in set(held):
                assert cache._blocks[b].ref == held.count(b), (step, b)
            for b in cache._free:
                assert cache._blocks[b].ref == 0, (step, b)
            assert len(set(cache._free)) == len(cache._free)
        for sid in list(live):
            cache.free(sid)
        assert cache.num_free_blocks == 16

    def test_out_of_blocks_is_loud(self):
        cache = BlockKVCache(num_layers=1, num_blocks=2, block_size=4,
                             num_heads=1, head_dim=2)
        cache.allocate("a", 8)
        with pytest.raises(BlockAllocatorError, match="out of KV blocks"):
            cache.allocate("b", 4)

    def test_swap_roundtrip_bit_exact(self):
        cache = BlockKVCache(num_layers=2, num_blocks=6, block_size=4,
                             num_heads=2, head_dim=3)
        cache.allocate("a", 7)
        rng = np.random.RandomState(5)
        for l in range(2):
            cache.k_blocks[l] = jnp.asarray(
                rng.randn(*cache.k_blocks[l].shape), jnp.float32)
            cache.v_blocks[l] = jnp.asarray(
                rng.randn(*cache.v_blocks[l].shape), jnp.float32)
        t0 = cache.block_table("a")
        want_k = [np.asarray(cache.k_blocks[l][np.asarray(t0)])
                  for l in range(2)]
        saved = cache.swap_out("a")
        assert cache.blocks_in_use == 0
        cache.allocate("x", 9)                 # churn the pool
        cache.free("x")
        cache.swap_in("a", saved)
        t1 = cache.block_table("a")
        for l in range(2):
            np.testing.assert_array_equal(
                np.asarray(cache.k_blocks[l][np.asarray(t1)]), want_k[l])


class TestPoolLayout:
    """The pools at rest are ``[num_blocks, block_size, H*D]`` — the shape
    the ragged kernel DMAs — and every block operation of the allocator
    moves a block's bytes (and an int8 block's scales) unchanged through
    it.  Content goes in and comes out through the ops the engine uses,
    as ``[.., H, D]`` token rows."""
    L, NB, BS, H, D = 2, 8, 4, 2, 4

    def _cache(self, quant):
        return BlockKVCache(self.L, self.NB, self.BS, self.H, self.D,
                            kv_quant=quant)

    def _write(self, cache, seq, start, n, seed):
        """Token rows ``[1, n, H, D]`` per layer at positions start.. of
        `seq`, through the engine's update ops; -> the K rows written."""
        from paddle_tpu.ops.paged_attention import (
            paged_cache_update_arrays, quantized_cache_update_arrays)

        rng = np.random.RandomState(seed)
        slots = jnp.asarray([[cache.slot(seq, start + i)
                              for i in range(n)]], jnp.int32)
        wrote = []
        for l in range(self.L):
            k = jnp.asarray(rng.randn(1, n, self.H, self.D), jnp.float32)
            if cache.kv_quant:
                cache.k_blocks[l], cache.k_scales[l] = \
                    quantized_cache_update_arrays(
                        cache.k_blocks[l], cache.k_scales[l], k, slots)
                cache.v_blocks[l], cache.v_scales[l] = \
                    quantized_cache_update_arrays(
                        cache.v_blocks[l], cache.v_scales[l], -k, slots)
            else:
                cache.k_blocks[l] = paged_cache_update_arrays(
                    cache.k_blocks[l], k, slots)
                cache.v_blocks[l] = paged_cache_update_arrays(
                    cache.v_blocks[l], -k, slots)
            wrote.append(np.asarray(k)[0])
        return wrote

    def _state(self, cache, seq, n):
        """Everything `seq`'s first `n` tokens are made of: the raw
        blocks (and scales) its table names, and the ``[n, H, D]`` view
        the attention ops gather."""
        from paddle_tpu.ops.paged_attention import (
            paged_gather_kv_arrays, quantized_gather_kv_arrays)

        idx = np.asarray(cache.block_table(seq)[:cache.blocks_needed(n)])
        tbl = jnp.asarray(idx[None], jnp.int32)
        out = []
        for l in range(self.L):
            for blocks, scales in (
                    (cache.k_blocks[l],
                     cache.k_scales[l] if cache.kv_quant else None),
                    (cache.v_blocks[l],
                     cache.v_scales[l] if cache.kv_quant else None)):
                out.append(np.asarray(blocks[idx]))
                if scales is None:
                    view = paged_gather_kv_arrays(blocks, tbl, self.H)
                else:
                    out.append(np.asarray(scales[idx]))
                    view = quantized_gather_kv_arrays(blocks, scales, tbl)
                assert view.shape == (1, len(idx) * self.BS, self.H, self.D)
                out.append(np.asarray(view)[0, :n])
        return out

    @staticmethod
    def _same(got, want):
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_pool_shape_at_rest(self, quant):
        cache = self._cache(quant)
        shape = (self.NB, self.BS, self.H * self.D)
        for l in range(self.L):
            assert cache.k_blocks[l].shape == shape
            assert cache.v_blocks[l].shape == shape
            assert cache.k_blocks[l].dtype == (jnp.int8 if quant
                                               else jnp.float32)
        assert (cache.num_heads, cache.head_dim) == (self.H, self.D)
        if quant:
            assert cache.k_scales[0].shape == (self.NB, self.H)
        # the engine's programs hand the pools back in the shape they took
        cache.allocate("a", 3)
        self._write(cache, "a", 0, 3, seed=0)
        assert cache.k_blocks[0].shape == cache.v_blocks[1].shape == shape

    def test_rows_land_heads_flattened(self):
        """Token p's ``[H, D]`` row is row ``slot(p)`` of the flattened
        pool, head h in lanes ``[h*D, (h+1)*D)``."""
        cache = self._cache(None)
        cache.allocate("a", 6)
        wrote = self._write(cache, "a", 0, 6, seed=1)
        for l in range(self.L):
            flat = np.asarray(cache.k_blocks[l]).reshape(
                self.NB * self.BS, self.H * self.D)
            for p in range(6):
                np.testing.assert_array_equal(
                    flat[cache.slot("a", p)], wrote[l][p].reshape(-1))
        view = self._state(cache, "a", 6)[1]
        np.testing.assert_array_equal(view, wrote[0])

    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_swap_roundtrip(self, quant):
        cache = self._cache(quant)
        cache.allocate("a", 7)
        self._write(cache, "a", 0, 7, seed=2)
        want = self._state(cache, "a", 7)
        saved = cache.swap_out("a")
        assert cache.blocks_in_use == 0
        assert saved["k"][0].shape == (2, self.BS, self.H * self.D)
        cache.allocate("x", 9)                 # churn the pool
        self._write(cache, "x", 0, 9, seed=3)
        cache.free("x")
        cache.swap_in("a", saved)
        self._same(self._state(cache, "a", 7), want)

    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_copy_on_write(self, quant):
        cache = self._cache(quant)
        cache.allocate("a", 6)                 # a partial last block
        self._write(cache, "a", 0, 6, seed=4)
        want = self._state(cache, "a", 6)
        cache.fork("a", "b")
        cache.grow_to("b", 7)                  # CoW of the shared block
        assert cache.block_table("a")[1] != cache.block_table("b")[1]
        self._same(self._state(cache, "b", 6), want)
        self._write(cache, "b", 6, 1, seed=5)  # b's own 7th token
        self._same(self._state(cache, "a", 6), want)

    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_prefix_adoption(self, quant):
        from paddle_tpu.serving.kv_cache import prefix_block_keys

        cache = self._cache(quant)
        keys = prefix_block_keys(list(range(8)), self.BS)
        cache.allocate("a", 8)                 # two full blocks
        self._write(cache, "a", 0, 8, seed=6)
        want = self._state(cache, "a", 8)
        cache.register_prefix("a", keys, 8)
        cache.free("a")                        # parked, still adoptable
        assert cache.match_prefix(keys) == 2
        assert cache.adopt_prefix("b", keys, 2) == 8
        self._same(self._state(cache, "b", 8), want)
        cache.grow_to("b", 9)                  # next token: a fresh block
        self._write(cache, "b", 8, 1, seed=7)
        self._same(self._state(cache, "b", 8), want)

    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_truncate_to(self, quant):
        cache = self._cache(quant)
        cache.allocate("a", 6)
        self._write(cache, "a", 0, 6, seed=8)
        cache.grow_to("a", 11)                 # five draft positions
        self._write(cache, "a", 6, 5, seed=9)
        want = self._state(cache, "a", 7)      # one draft accepted
        cache.truncate_to("a", 7)
        assert len(cache.block_table("a")) == 2 and cache.blocks_in_use == 2
        self._same(self._state(cache, "a", 7), want)
        cache.grow_to("a", 9)                  # decoding goes on
        self._write(cache, "a", 7, 2, seed=10)
        assert len(self._state(cache, "a", 9)[-1]) == 9


class TestChunkedPrefill:
    def test_chunked_prefill_matches_unchunked_engine(self, model):
        """Chunked prefill (token-budget admission) is mathematically the
        same program with reassociated float reductions; on this machine
        the greedy stream is deterministic either way, and the two engine
        configurations must agree."""
        rng = np.random.RandomState(4)
        prompt = rng.randint(0, model.cfg.vocab_size, (13,)).astype(np.int32)
        whole = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=1))
        [a] = whole.generate([prompt], SamplingParams(max_new_tokens=NEW))
        chunked = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=1, max_num_batched_tokens=5))
        [b] = chunked.generate([prompt], SamplingParams(max_new_tokens=NEW))
        np.testing.assert_array_equal(a, b)


class TestMonitorAndSmoke:
    def test_serving_metrics_in_snapshot(self, model, prompts):
        monitor.enable(True)
        try:
            eng = LLMEngine(model, EngineConfig(block_size=16,
                                                max_num_seqs=4))
            eng.generate(prompts[:2], SamplingParams(max_new_tokens=2))
            snap = monitor.snapshot()
        finally:
            monitor.refresh()
        for name in ("serving/queue_depth", "serving/running",
                     "serving/blocks_in_use", "serving/block_utilization",
                     "serving/prefill_tokens", "serving/decode_tokens",
                     "serving/prefill_tps", "serving/decode_tps",
                     "serving/requests_finished", "serving/step_time"):
            assert name in snap, sorted(k for k in snap
                                        if k.startswith("serving/"))
        assert snap["serving/decode_tokens"] >= 2
        assert snap["serving/blocks_in_use"] == 0   # all freed at the end

    def test_serve_smoke_script(self):
        # --trace: the ISSUE-5 observability acceptance (ttft/tpot
        # percentiles, parent-linked request trace, chrome export, live
        # endpoint), --perf: the host-phase table, padding/goodput
        # gauges and compiles FLAT across a 3→5 batch crossing, and
        # --prefix-cache --spec: the prefix and speculation one
        # (hit_tokens == (N-1)*prefix_len, accept_rate > 0 with >1
        # token per decode step, compiles FLAT across hit/miss and
        # spec rounds), and --slo: the ISSUE-16 one (deadline request
        # traceable reqlog -> kept trace -> exemplar -> burn rate on
        # replica and fleet), and --api: the ISSUE-19 one (socket-streamed
        # /v1/completions token-identical to generate() greedy AND
        # seeded, tenant-labeled metrics on /metrics, 429 shed under
        # burn), and --memobs: the ISSUE-20 one (/kv + /memory/timeline
        # live, an eviction storm yielding EXACTLY ONE rate-limited
        # kv_pressure dump naming the actual top holder, a suppressed
        # admission-failure trigger, compiles FLAT under pressure) all assert in-script ON TOP of the plain smoke
        # checks, so ONE subprocess covers every leg (tests/test_trace.py
        # and tests/test_perf.py lean on this invocation; tier-1 budget
        # leaves no room for a second engine-compiling subprocess)
        script = (pathlib.Path(__file__).resolve().parent.parent
                  / "scripts" / "serve_smoke.py")
        env = {k: v for k, v in os.environ.items()
               if k not in ("PYTHONPATH", "XLA_FLAGS", "PTPU_FAULTS")}
        env["PTPU_FORCE_PLATFORM"] = "cpu"
        env["JAX_PLATFORMS"] = "cpu"
        env["PTPU_MONITOR"] = "1"
        proc = subprocess.run([sys.executable, str(script), "--trace",
                               "--perf", "--prefix-cache", "--spec",
                               "--slo", "--api", "--memobs"],
                              env=env, capture_output=True, text=True,
                              timeout=560)
        assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
        assert "OK" in proc.stdout
        assert "tokens/s" in proc.stdout
        assert "ttft:" in proc.stdout and "request 0 trace:" in proc.stdout
        assert "chrome trace:" in proc.stdout
        assert "serving host phases" in proc.stdout
        assert "compiles FLAT across 3→5 crossing" in proc.stdout
        assert "prefix cache: hits=3 hit_tokens=96" in proc.stdout
        assert "compiles FLAT across hit/miss round" in proc.stdout
        assert "accept_rate=" in proc.stdout
        assert "compiles FLAT across spec round" in proc.stdout
        # ISSUE 16 --slo leg: deadline request -> reqlog event + kept
        # trace, live + fleet-merged burn rate, federated exemplars
        assert "finish=deadline" in proc.stdout
        assert "worst fast burn" in proc.stdout
        assert "exemplars federated" in proc.stdout
        # ISSUE 19 --api leg: streamed parity, tenant metrics, shed 429
        assert "token-identical to generate()" in proc.stdout
        assert "serving_tenant_* series live" in proc.stdout
        assert "best-effort shed with 429 code=shed" in proc.stdout
        # ISSUE 20 --memobs leg: pool map + timeline live, one dump
        # naming the top holder, rate-limited second trigger, FLAT
        assert "memobs: /kv pool map live" in proc.stdout
        assert "eviction storm -> one kv_pressure dump, top holder" \
            in proc.stdout
        assert "tenant=acme" in proc.stdout
        assert "admission failure inside cooldown suppressed" \
            in proc.stdout
        assert "compiles FLAT under pressure" in proc.stdout


class TestPagedAttentionOp:
    def test_matches_cached_attention_reference(self):
        """ops.paged_attention vs the dense-ring decode oracle
        (`cached_attention_arrays`, models/gpt.py:326): same tokens in
        blocks ⇒ bitwise-identical output."""
        from paddle_tpu.ops.pallas_ops import cached_attention_arrays
        from paddle_tpu.ops.paged_attention import (
            paged_attention_arrays, paged_cache_update_arrays,
            slot_mapping)

        rng = np.random.RandomState(0)
        B, H, D, BS, NB = 2, 2, 4, 4, 12
        s_max = 16
        lens = np.asarray([6, 9], np.int32)     # context BEFORE the token
        # dense oracle: contiguous [B, S_max, H*D] rings
        kd = rng.randn(B, s_max, H * D).astype(np.float32)
        vd = rng.randn(B, s_max, H * D).astype(np.float32)
        kd[0, lens[0]:] = 0.0
        vd[0, lens[0]:] = 0.0
        kd[1, lens[1]:] = 0.0
        vd[1, lens[1]:] = 0.0
        q = rng.randn(B, 1, H, D).astype(np.float32)
        k_new = rng.randn(B, 1, H, D).astype(np.float32)
        v_new = rng.randn(B, 1, H, D).astype(np.float32)
        # paged pool holding the same tokens at scattered physical blocks
        tables = np.asarray([[7, 2, 5, 9], [1, 8, 3, 0]], np.int32)
        kb = np.zeros((NB, BS, H * D), np.float32)
        vb = np.zeros((NB, BS, H * D), np.float32)
        for b in range(B):
            for p in range(int(lens[b])):
                kb[tables[b][p // BS], p % BS] = kd[b, p]
                vb[tables[b][p // BS], p % BS] = vd[b, p]
        # oracle: per-row dense decode at its own scalar t
        want = []
        for b in range(B):
            o, _, _ = cached_attention_arrays(
                jnp.asarray(q[b:b + 1]), jnp.asarray(k_new[b:b + 1]),
                jnp.asarray(v_new[b:b + 1]), jnp.asarray(kd[b:b + 1]),
                jnp.asarray(vd[b:b + 1]), int(lens[b]))
            want.append(np.asarray(o))
        # paged: write-then-attend over the ragged pair in ONE call
        slots = slot_mapping(tables, lens[:, None], BS, NB * BS)
        kb2 = paged_cache_update_arrays(jnp.asarray(kb),
                                        jnp.asarray(k_new), slots)
        vb2 = paged_cache_update_arrays(jnp.asarray(vb),
                                        jnp.asarray(v_new), slots)
        got = paged_attention_arrays(jnp.asarray(q), kb2, vb2,
                                     jnp.asarray(tables),
                                     jnp.asarray(lens))
        for b in range(B):
            np.testing.assert_array_equal(np.asarray(got[b:b + 1]),
                                          want[b])

    @pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
    @pytest.mark.parametrize("s_len,start", [(1, 0), (1, 3), (3, 2), (8, 0),
                                             (10, 0), (7, 5), (5, 4)])
    def test_writers_move_blocks_and_change_only_the_named_slots(
            self, s_len, start, quant):
        """The writers read, merge and write the touched blocks whole
        (`_block_window`); what lands is a plain scatter of the rows,
        from any offset in a block: every slot not named is bit for bit
        what it was, a row with a short valid prefix writes only that,
        an inactive row nothing."""
        from paddle_tpu.ops.paged_attention import (
            paged_cache_update_arrays, quantized_cache_update_arrays,
            slot_mapping)

        rng = np.random.RandomState(10 * s_len + start)
        B, H, D, BS, NB = 3, 2, 4, 4, 16
        rows = rng.randn(B, s_len, H, D).astype(np.float32)
        tables = np.asarray([[7, 2, 5, 11], [1, 8, 3, 12], [0, 4, 6, 13]],
                            np.int32)
        pos = start + np.tile(np.arange(s_len, dtype=np.int32), (B, 1))
        keep = [s_len, max(s_len - 2, 0), 0]        # valid prefix per row
        valid = np.arange(s_len)[None] < np.asarray(keep)[:, None]
        slots = slot_mapping(tables, pos, BS, NB * BS, valid=valid)
        named = np.asarray(slots)[valid]
        if quant:
            pool = jnp.asarray(rng.randint(-127, 128, (NB, BS, H * D)),
                               jnp.int8)
            # a scale above the rows' amax / 127: nothing is rescaled
            scales = jnp.full((NB, H), 1.0, jnp.float32)
            got, sc = quantized_cache_update_arrays(pool, scales,
                                                    jnp.asarray(rows), slots)
            np.testing.assert_array_equal(np.asarray(sc), np.asarray(scales))
            want = np.array(pool).reshape(NB * BS, H * D)
            want[named] = np.round(rows[valid]).reshape(-1, H * D)
        else:
            pool = jnp.asarray(rng.randn(NB, BS, H * D), jnp.float32)
            got = paged_cache_update_arrays(pool, jnp.asarray(rows), slots)
            want = np.array(pool).reshape(NB * BS, H * D)
            want[named] = rows[valid].reshape(-1, H * D)
        np.testing.assert_array_equal(
            np.asarray(got).reshape(NB * BS, H * D), want)

    def test_writers_refuse_slots_that_are_not_consecutive(self):
        from paddle_tpu.ops.paged_attention import paged_cache_update_arrays

        pool = jnp.zeros((4, 4, 2), jnp.float32)
        rows = jnp.ones((1, 3, 1, 2), jnp.float32)
        for bad in ([[0, 2, 3]], [[16, 1, 2]], [[3, 5, 6]]):
            with pytest.raises(ValueError, match="consecutive"):
                paged_cache_update_arrays(pool, rows,
                                          jnp.asarray(bad, jnp.int32))
        # the end of one block, then the start of ANY other: fine
        out = paged_cache_update_arrays(pool, rows,
                                        jnp.asarray([[3, 8, 9]], jnp.int32))
        assert float(out.sum()) == 6.0

    def test_oob_slots_are_dropped_not_clamped(self):
        from paddle_tpu.ops.paged_attention import paged_cache_update_arrays

        kb = jnp.zeros((2, 2, 1), jnp.float32)
        rows = jnp.ones((1, 1, 1, 1), jnp.float32)
        out = paged_cache_update_arrays(kb, rows,
                                        jnp.asarray([[4]], jnp.int32))
        np.testing.assert_array_equal(np.asarray(out), np.asarray(kb))


class TestEngineGuards:
    def test_inference_namespace_entry_point(self):
        from paddle_tpu import inference

        assert inference.LLMEngine is LLMEngine
        assert inference.SamplingParams is SamplingParams
        assert inference.BlockKVCache is BlockKVCache

    def test_requires_stacked_blocks(self):
        cfg = gpt_test_config(stacked_blocks=False, sequence_parallel=False)
        paddle.seed(0)
        m = GPTForCausalLM(cfg)
        with pytest.raises(ValueError, match="stacked_blocks"):
            LLMEngine(m)

    def test_rejects_overlong_request(self, model, engine):
        with pytest.raises(ValueError, match="max_model_len"):
            engine.add_request(list(range(60)),
                               SamplingParams(max_new_tokens=60))


# -- ISSUE 31: a step crosses to the device a fixed number of times ----------

from paddle_tpu.serving.scheduler import Request  # noqa: E402


@pytest.fixture
def monitored():
    monitor.enable(True)
    yield
    monitor.refresh()


def _device_calls():
    """(h2d, d2h) of `serving/device_calls` so far."""
    snap = monitor.snapshot().get("serving/device_calls", {})
    return np.array([snap.get("dir=h2d", 0), snap.get("dir=d2h", 0)], int)


def _watch_schedule(eng):
    """Record (kind, rows) of every step the engine's scheduler decides."""
    kinds = []
    inner = eng.scheduler.schedule

    def schedule():
        out = inner()
        kinds.append((out.kind, len(out.decode_requests)))
        return out

    eng.scheduler.schedule = schedule
    return kinds


def _step_calls(eng):
    before = _device_calls()
    eng.step()
    return tuple(_device_calls() - before)


@pytest.fixture(scope="module", params=["gpt-one-group", "afmoe-two-groups"])
def family(request, model):
    """(model, EngineConfig keywords): GPT tiny is one cache group, afmoe
    tiny (tests/test_afmoe_serving.py's size) a full group and a window
    group of 8 tokens."""
    if request.param == "gpt-one-group":
        return model, dict(block_size=16)
    from paddle_tpu.models import AfmoeForCausalLM, afmoe_test_config

    paddle.seed(0)
    m = AfmoeForCausalLM(afmoe_test_config())
    m.eval()
    return m, dict(block_size=4, max_model_len=64)


class TestDeviceCrossings:
    def test_a_step_crosses_the_same_number_of_times_at_any_batch(
            self, family, monitored):
        """A decode step uploads twice (model inputs, sampler inputs) and
        reads back once at 1 live row and at `max_num_seqs` live rows; a
        whole-prompt prefill step the same whatever the prompt's length
        and however many running rows it carries.
        A call of `step()` makes the uploads of the step it dispatches and
        the readback of the step before: the first call reads nothing
        back, the last one (nothing left to dispatch) uploads nothing."""
        m, kw = family
        eng = LLMEngine(m, EngineConfig(max_num_seqs=4, **kw))
        assert list(eng.caches) in (["full"], ["full", "window"])
        kinds = _watch_schedule(eng)
        rng = np.random.RandomState(3)
        sp = SamplingParams(max_new_tokens=8)
        ids = [eng.add_request(rng.randint(0, m.cfg.vocab_size, (5,)), sp)]
        assert _step_calls(eng) == (2, 0) and kinds[-1] == ("prefill", 0)
        assert _step_calls(eng) == (2, 1) and kinds[-1] == ("decode", 1)
        for n in (3, 11, 17):          # 17 passes afmoe tiny's window of 8
            ids.append(eng.add_request(
                rng.randint(0, m.cfg.vocab_size, (n,)), sp))
        seen = set()
        while eng.has_unfinished():
            decided = len(kinds)
            calls = _step_calls(eng)
            if len(kinds) == decided:      # nothing left to schedule
                assert calls == (0, 1) and not eng.has_unfinished()
                break
            assert calls == (2, 1), (kinds[-1], calls)
            seen.add(kinds[-1])
        # each prompt added while rows decode carries them all
        assert ("decode", 4) in seen and ("prefill", 3) in seen
        assert "idle" not in {k for k, _ in kinds}
        for i in ids:
            eng.release_request(i)

    def test_a_prefill_chunk_that_samples_nothing_uploads_once(
            self, family, monitored):
        m, kw = family
        eng = LLMEngine(m, EngineConfig(max_num_seqs=2,
                                        max_num_batched_tokens=8, **kw))
        kinds = _watch_schedule(eng)
        rid = eng.add_request(list(range(1, 20)),
                              SamplingParams(max_new_tokens=2))
        assert _step_calls(eng) == (1, 0)      # positions 0-7
        assert _step_calls(eng) == (1, 0)      # 8-15
        assert _step_calls(eng) == (2, 0)      # 16-18 and the first token,
        assert [k for k, _ in kinds] == ["prefill"] * 3
        # which is read back behind the decode step's two uploads
        assert _step_calls(eng) == (2, 1) and kinds[-1] == ("decode", 1)
        eng.release_request(rid)
        assert not eng.has_unfinished()

    def test_add_request_reads_the_device_for_a_sampling_key_only(
            self, engine, monitored):
        before = _device_calls()
        a = engine.add_request([1, 2, 3], SamplingParams(max_new_tokens=2))
        assert tuple(_device_calls() - before) == (0, 0)
        b = engine.add_request([1, 2, 3], SamplingParams(
            max_new_tokens=2, do_sample=True, seed=11))
        assert tuple(_device_calls() - before) == (0, 1)
        for i in (a, b):
            engine.release_request(i)


class TestStepInFlight:
    """ISSUE 35: `step()` dispatches the step it has scheduled before it
    reads back the one before.  The scenarios are tests/_step_in_flight.py's
    (lfm2's state slots and mistral4's latent group run them in their own
    files)."""

    @staticmethod
    def _make(family, **over):
        m, kw = family
        return lambda: LLMEngine(m, EngineConfig(
            **dict(kw, max_num_seqs=3, **over)))

    def test_tokens_and_keys_equal_the_settled_engines(self, family,
                                                       monitored):
        sif.check_tokens_and_keys(self._make(family), family[0].cfg.vocab_size)

    def test_eos_mid_flight(self, family, monitored):
        sif.check_eos_mid_flight(self._make(family), family[0].cfg.vocab_size)

    def test_cancel_and_deadline_with_a_step_owed(self, family, monitored):
        sif.check_cancel_and_deadline(self._make(family),
                                      family[0].cfg.vocab_size)

    def test_forced_preemption_with_a_step_owed(self, family, monitored):
        sif.check_forced_preemption(
            self._make(family, block_size=4, num_blocks=12),
            family[0].cfg.vocab_size)


class TestStepInFlightCounters:
    def test_a_steady_batch_keeps_a_step_in_flight(self, family, monitored):
        """`serving/steps_dispatched{in_flight}`: after the first step of
        a steady batch every step is dispatched behind one still owed, and
        nothing settles until the batch ends; `serving/step_time{phase}`
        counts each program step once, under the kind of the step READ
        BACK by the call (the one dispatched a call earlier)."""
        m, kw = family
        eng = LLMEngine(m, EngineConfig(max_num_seqs=3, **kw))
        kinds = _watch_schedule(eng)
        steps0 = sif.counter("serving/steps_dispatched")
        settles0 = sif.counter("serving/settles")
        ps = sif.prompts(m.cfg.vocab_size)
        rids = [eng.add_request(p, SamplingParams(max_new_tokens=6))
                for p in ps[:3]]
        read_back = []
        while eng.has_unfinished():
            before = {k: v["count"] for k, v in monitor.snapshot().get(
                "serving/step_time", {}).items()}
            eng.step()
            after = {k: v["count"] for k, v in monitor.snapshot().get(
                "serving/step_time", {}).items()}
            read_back.append([k[len("phase="):] for k in after
                              if after[k] != before.get(k, 0)])
        # 3 prefills and 5 decode steps, then a call with nothing to
        # schedule; call n observes the step that call n - 1 dispatched
        dispatched = [k for k, _ in kinds]
        assert dispatched == ["prefill"] * 3 + ["decode"] * 5
        assert read_back == [[]] + [[k] for k in dispatched]
        assert sif.moved("serving/steps_dispatched", steps0) == {
            "in_flight=0": 1, "in_flight=1": 7}
        assert sif.moved("serving/settles", settles0) == {"why=idle": 1}
        for r in rids:
            assert len(eng.request_output(r)) == len(ps[rids.index(r)]) + 6
            eng.release_request(r)

    def test_speculation_settles_every_step(self, model, monitored):
        """The drafts of step k+1 are made from step k's tokens: with
        `speculative_tokens` on, every step is read back where it is
        dispatched, and the engine decides that from its own
        configuration."""
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=3,
                                            speculative_tokens=2))
        steps0 = sif.counter("serving/steps_dispatched")
        settles0 = sif.counter("serving/settles")
        ps = sif.prompts(model.cfg.vocab_size)
        rids = [eng.add_request(np.tile(p, 3),
                                SamplingParams(max_new_tokens=8))
                for p in ps[:2]]
        calls = 0
        while eng.has_unfinished():
            eng.step()
            calls += 1
            assert all(r.owed == 0 for r in eng._requests.values())
        assert sif.moved("serving/steps_dispatched", steps0) == {
            "in_flight=0": calls}
        assert sif.moved("serving/settles", settles0) == {"why=spec": calls}
        for r in rids:
            eng.release_request(r)

    @pytest.mark.parametrize("mode", ["in_flight", "settled_each_call",
                                      "speculation"])
    def test_step_record_once_a_program_step(self, model, monitored, mode):
        """ISSUE 36: `serving/step_wait{phase}` is observed where
        `serving/step_time{phase}` is, so a step read back behind the
        next dispatch, one settled by the caller after its call (what a
        `prefill`-role replica does) and a speculative step (settled
        where it is dispatched) each count once, a call that only reads
        back dispatches nothing, and no wait is longer than its step."""
        def hist(name):
            return {k: (v["count"], v["sum"]) for k, v in
                    monitor.snapshot().get(name, {}).items()}

        spec = 2 if mode == "speculation" else 0
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=3,
                                            speculative_tokens=spec))
        ps = sif.prompts(model.cfg.vocab_size)
        rids = [eng.add_request(np.tile(p, 3),
                                SamplingParams(max_new_tokens=6))
                for p in ps[:2]]
        steps0 = sum(sif.counter("serving/steps_dispatched").values())
        time0, wait0 = hist("serving/step_time"), hist("serving/step_wait")
        while eng.has_unfinished():
            eng.step()
            if mode == "settled_each_call":
                eng.settle()
        dispatched = sum(
            sif.counter("serving/steps_dispatched").values()) - steps0
        time1, wait1 = hist("serving/step_time"), hist("serving/step_wait")
        assert "phase=idle" not in wait1
        counted = 0
        for kind in ("phase=prefill", "phase=decode"):
            n, waited = (a - b for a, b in zip(
                wait1[kind], wait0.get(kind, (0, 0.0))))
            m, lasted = (a - b for a, b in zip(
                time1[kind], time0.get(kind, (0, 0.0))))
            assert n == m > 0 and 0 < waited <= lasted
            counted += n
        assert counted == dispatched
        for r in rids:
            eng.release_request(r)

    def test_public_reads_settle_and_say_why(self, engine, monitored):
        settles0 = sif.counter("serving/settles")
        ps = sif.prompts(engine.cfg.vocab_size)
        sp = SamplingParams(max_new_tokens=8)
        a, b = (engine.add_request(p, sp) for p in ps[:2])
        for _ in range(3):      # a's prefill, b's (a rides it), a decode
            engine.step()
        assert engine._requests[a].owed == 1
        assert len(engine.request_output(a)) == len(ps[0]) + 3
        assert engine._requests[a].owed == 0 and engine._flight is None
        engine.step()
        child = engine.fork_request(a, sp)
        engine.step()
        engine.settle()
        engine.settle()                 # nothing in flight: counts nothing
        assert sif.moved("serving/settles", settles0) == {
            "why=export": 1, "why=fork": 1, "why=drain": 1}
        for r in (a, b, child):
            engine.release_request(r)
        while engine.has_unfinished():
            engine.step()


def _parent_padded_table(k, seq_id, width):
    """The parent's `BlockKVCache.padded_table`: the block table padded to
    `width` entries with `num_blocks`, by list concatenation."""
    t = k.block_table(seq_id)
    return t + [k.num_blocks] * (width - len(t))


def _parent_decode_inputs(eng, rows, drafts, bb, cw):
    """`LLMEngine._decode_inputs` as the parent of PR 31 built it, row by
    row: a padded list a row, one `slot()` call a position."""
    toks = np.zeros((bb, cw), np.int32)
    pos0 = np.zeros((bb,), np.int32)
    lens = np.zeros((bb,), np.int32)
    caches = list(eng.caches.values())
    tables = [np.full((bb, eng.blocks_per_seq), k.num_blocks, np.int32)
              for k in caches]
    slots = [np.full((bb, cw), k.num_slots, np.int32) for k in caches]
    for i, req in enumerate(rows):
        toks[i, 0] = req.output_ids[-1] if req.output_ids \
            else req.prompt_ids[-1]
        m = len(drafts[i])
        if m:
            toks[i, 1:1 + m] = drafts[i]
        p = req.total_len - 1
        pos0[i] = p
        lens[i] = req.total_len + m
        for k, tbl, slt in zip(caches, tables, slots):
            tbl[i] = _parent_padded_table(k, req.req_id, eng.blocks_per_seq)
            for j in range(1 + m):
                slt[i, j] = k.slot(req.req_id, p + j)
    return toks, pos0, lens, tuple(tables), tuple(slots)


def _assert_same_inputs(got, want):
    for g, w in zip(got[:3], want[:3]):
        assert isinstance(g, np.ndarray) and g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)
    for g_group, w_group in zip(got[3:], want[3:]):
        assert len(g_group) == len(w_group)
        for g, w in zip(g_group, w_group):
            assert isinstance(g, np.ndarray) and g.dtype == np.int32
            assert g.shape == w.shape
            np.testing.assert_array_equal(g, w)


class TestStepInputsByArrayArithmetic:
    """`_decode_inputs` and the prefill body's slot row against the
    parent's row-by-row construction, kept above."""

    def _rows(self, eng, shapes):
        """Requests of (prompt, emitted, room for drafts) tokens, their
        blocks taken from the engine's allocator the way the scheduler
        does: a whole prompt's tail, then one `grow_to` a decode step."""
        rows = []
        for rid, (plen, nout, room) in enumerate(shapes):
            req = Request(rid, [7 + rid + t for t in range(plen)],
                          SamplingParams())
            eng.kv.allocate(rid, plen, tail_only=True)
            for t in range(nout):
                req.output_ids.append(100 + rid + t)
                eng.kv.grow_to(rid, req.total_len + room)
            rows.append(req)
        return rows

    @pytest.mark.parametrize("cw", [1, 4])
    def test_decode_inputs_equal_the_parents(self, family, cw):
        m, kw = family
        eng = LLMEngine(m, EngineConfig(max_num_seqs=6, **kw))
        # short, at a block's edge, past afmoe tiny's window of 8 (its
        # window group has given blocks back), and two rows left unused
        rows = self._rows(eng, [(3, 1, cw - 1), (4, 4, cw - 1),
                                (13, 9, cw - 1), (21, 6, cw - 1)])
        drafts = [[], [5], [9, 8, 7], [4, 4]] if cw > 1 else [()] * 4
        got = eng._decode_inputs(rows, drafts, 6, cw)
        _assert_same_inputs(got, _parent_decode_inputs(eng, rows, drafts,
                                                       6, cw))
        for k, tbl, slt in zip(eng.caches.values(), got[3], got[4]):
            assert (tbl[4:] == k.num_blocks).all()       # padding rows
            assert (slt[4:] == k.num_slots).all()
            if cw > 1:                                   # unused drafts
                assert (slt[0, 1:] == k.num_slots).all()
                assert (slt[1, 2:] == k.num_slots).all()
        if len(eng.caches) == 2:
            win = eng.caches["window"]
            assert win.released > 0
            assert (got[3][1][2] == win.num_blocks).any()   # given back

    def test_a_table_wider_than_the_programs_raises(self, model):
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2))
        row, = self._rows(eng, [(3, 1, 0)])
        eng.cache._tables[0] += [0] * eng.blocks_per_seq
        with pytest.raises(BlockAllocatorError, match="table width"):
            eng._decode_inputs([row], [()], 2, 1)

    @pytest.mark.parametrize("plen,start", [(5, 0), (30, 0), (20, 12)])
    def test_prefill_slot_row_equals_the_parents(self, family, plen, start):
        """A whole prompt writes a window group from `tail_start` on; a
        later chunk writes from where it starts."""
        m, kw = family
        eng = LLMEngine(m, EngineConfig(max_num_seqs=2, **kw))
        whole = start == 0
        if whole:
            eng.kv.allocate(0, plen, tail_only=True)
        else:                       # the chunks before, then this one
            eng.kv.allocate(0, start)
            eng.kv.grow_to(0, plen)
        for k in eng.caches.values():
            lo = k.tail_start(plen) if whole else start
            want = np.asarray([[k.slot(0, p) for p in range(lo, plen)]],
                              np.int32)
            got = eng._slot_row(k, 0, lo, plen)
            assert isinstance(got, np.ndarray) and got.dtype == np.int32
            assert got.shape == want.shape == (1, plen - lo)
            np.testing.assert_array_equal(got, want)
            np.testing.assert_array_equal(
                eng._table_row(k, 0),
                np.asarray(_parent_padded_table(k, 0, eng.blocks_per_seq),
                           np.int32))


def _is_host_key(key):
    return (isinstance(key, np.ndarray) and key.dtype == np.uint32
            and key.shape == (2,))


class TestKeysRestOnTheHost:
    def test_key_is_host_words_from_admission_to_adoption(self, model):
        import jax

        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4))
        sp = SamplingParams(max_new_tokens=6, do_sample=True, seed=5,
                            temperature=0.9)
        greedy = eng._requests[eng.add_request(
            [1, 2, 3], SamplingParams(max_new_tokens=6))]
        seeded = eng._requests[eng.add_request([4, 5, 6, 7], sp)]
        unseeded = eng._requests[eng.add_request(
            [4, 5], SamplingParams(max_new_tokens=6, do_sample=True))]
        assert _is_host_key(greedy.key) and not greedy.key.any()
        np.testing.assert_array_equal(
            greedy.key, np.asarray(jax.random.PRNGKey(0)))
        assert _is_host_key(seeded.key) and _is_host_key(unseeded.key)
        np.testing.assert_array_equal(
            seeded.key, np.asarray(jax.random.PRNGKey(5)))
        for _ in range(4):                    # three prefills and a decode
            eng.step()
        assert all(_is_host_key(r.key) for r in (greedy, seeded, unseeded))
        assert not greedy.key.any()           # a greedy row's key stands
        assert (seeded.key != np.asarray(jax.random.PRNGKey(5))).any()
        child = eng._requests[eng.fork_request(seeded.req_id, sp)]
        assert _is_host_key(child.key)
        handoff = eng.export_request(seeded.req_id)
        assert _is_host_key(handoff["key"])
        np.testing.assert_array_equal(handoff["key"], seeded.key)
        other = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4))
        adopted = other._requests[other.adopt_request(
            handoff["prompt_ids"], handoff["params"], handoff["output_ids"],
            list(handoff["key"]), handoff["kv"])]
        assert _is_host_key(adopted.key)
        np.testing.assert_array_equal(adopted.key, handoff["key"])
        other.step()
        other.settle()              # the host's copy arrives a step late
        assert _is_host_key(adopted.key)
        assert (adopted.key != handoff["key"]).any()   # handoff not aliased
