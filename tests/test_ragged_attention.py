"""ops.ragged_paged_attention + the engine's ragged decode path (ISSUE 8).

The bars:

- the XLA fallback is BITWISE the `paged_cache_update_arrays` +
  `paged_attention_arrays` composition (fp — that is the engine parity
  contract) and bitwise on the quantized UPDATE with an
  algebraically-identical scale-folded attention (int8, documented
  last-ulp reassociation) — across row mixes: all-decode,
  all-prefill-chunk, mixed, single row, padding/evicted row mid-batch;
- the Pallas kernel (interpret mode, CPU, fast tier) writes pools and
  scales bit-identically to the references and matches the fallback's
  attention within float tolerance;
- the engine's ragged path is token-identical to solo dense
  `generate()` (greedy + fixed-seed sampling), fp32 and int8 KV per the
  PR-2/PR-4 conventions;
- ONE compiled decode program regardless of batch composition: driving
  the engine across a power of two of running rows leaves
  `serving/compiles` and `jit/recompiles{fn=serving:*}` FLAT;
- the int8 ragged path never runs the separate dequant pass
  (`lowbit/dequant_calls{site="paged_gather"}` stays absent).
"""
import zlib

import numpy as np
import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.models import GPTForCausalLM, gpt_test_config
from paddle_tpu.ops.paged_attention import (paged_attention_arrays,
                                            paged_cache_update_arrays,
                                            quantized_cache_update_arrays)
from paddle_tpu.ops import ragged_paged_attention as rp
from paddle_tpu.serving import EngineConfig, LLMEngine, SamplingParams

NEW = 5
LENS = [3, 5, 7, 3, 5, 7, 4, 4]


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


# ---------------------------------------------------------------------------
# op level: fallback vs the reference composition, across row mixes
# ---------------------------------------------------------------------------

def _mix(name, bs=4, nb=12, maxb=4):
    """Build (q, k_new, v_new, tables, pos0, lens, slots, C) for a named
    row mix.  pos0 is the first-query position; lens the post-write key
    count; padding entries get slot == num_slots (dropped)."""
    # crc32, not hash(): the builtin is PYTHONHASHSEED-salted, which
    # would make a failing draw unreproducible across processes
    rng = np.random.RandomState(zlib.crc32(name.encode()) % 2**31)
    if name == "all_decode":
        rows = [(6, 1), (9, 1), (1, 1)]          # (kv_len after write, q)
    elif name == "all_prefill_chunk":
        rows = [(4, 4), (8, 4)]
    elif name == "mixed":
        rows = [(4, 4), (9, 1), (13, 2)]         # chunk + decode + chunk
    elif name == "single_row":
        rows = [(7, 1)]
    elif name == "evicted_mid_batch":
        rows = [(6, 1), None, (9, 1)]            # padding row between
    else:
        raise AssertionError(name)
    C = max(q for r in rows if r is not None for q in (r[1],))
    B = len(rows)
    H, D = 2, 4
    num_slots = nb * bs
    tables = np.full((B, maxb), nb, np.int32)
    pos0 = np.zeros((B,), np.int32)
    lens = np.zeros((B,), np.int32)
    slots = np.full((B, C), num_slots, np.int32)
    used = list(rng.permutation(nb))
    for b, r in enumerate(rows):
        if r is None:
            continue
        kv_len, q_len = r
        nblk = -(-kv_len // bs)
        tables[b, :nblk] = [used.pop() for _ in range(nblk)]
        pos0[b] = kv_len - q_len
        lens[b] = kv_len
        for i in range(q_len):
            p = pos0[b] + i
            slots[b, i] = tables[b, p // bs] * bs + p % bs
    q = rng.randn(B, C, H, D).astype(np.float32)
    kn = rng.randn(B, C, H, D).astype(np.float32)
    vn = rng.randn(B, C, H, D).astype(np.float32)
    valid = [b for b, r in enumerate(rows) if r is not None]
    qlens = [0 if r is None else r[1] for r in rows]
    return (jnp.asarray(q), jnp.asarray(kn), jnp.asarray(vn),
            jnp.asarray(tables), jnp.asarray(pos0), jnp.asarray(lens),
            jnp.asarray(slots), valid, qlens, (nb, bs, H, D))


MIXES = ["all_decode", "all_prefill_chunk", "mixed", "single_row",
         "evicted_mid_batch"]


class TestFallbackVsReference:
    @pytest.mark.parametrize("mix", MIXES)
    def test_fp_bitwise(self, mix):
        q, kn, vn, tables, pos0, lens, slots, valid, qlens, geo = _mix(mix)
        nb, bs, H, D = geo
        rng = np.random.RandomState(1)
        kb = jnp.asarray(rng.randn(nb, bs, H * D), jnp.float32)
        vb = jnp.asarray(rng.randn(nb, bs, H * D), jnp.float32)
        k2r = paged_cache_update_arrays(kb, kn, slots)
        v2r = paged_cache_update_arrays(vb, vn, slots)
        want = paged_attention_arrays(q, k2r, v2r, tables, pos0)
        out, k2, v2 = rp.ragged_paged_attention_arrays(
            q, kn, vn, kb, vb, tables, pos0, lens, slots)
        np.testing.assert_array_equal(np.asarray(k2), np.asarray(k2r))
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(v2r))
        for b in valid:
            np.testing.assert_array_equal(
                np.asarray(out[b, :qlens[b]]),
                np.asarray(want[b, :qlens[b]]), err_msg=f"{mix} row {b}")

    @pytest.mark.parametrize("mix", MIXES)
    def test_int8_update_bitwise_attention_close(self, mix):
        q, kn, vn, tables, pos0, lens, slots, valid, qlens, geo = _mix(mix)
        nb, bs, H, D = geo
        rng = np.random.RandomState(2)
        kb = jnp.asarray(rng.randint(-127, 128, (nb, bs, H * D)), jnp.int8)
        vb = jnp.asarray(rng.randint(-127, 128, (nb, bs, H * D)), jnp.int8)
        ks = jnp.asarray(rng.rand(nb, H) * 0.2, jnp.float32)
        vs = jnp.asarray(rng.rand(nb, H) * 0.2, jnp.float32)
        k2r, ks2r = quantized_cache_update_arrays(kb, ks, kn, slots)
        v2r, vs2r = quantized_cache_update_arrays(vb, vs, vn, slots)
        want = paged_attention_arrays(q, k2r, v2r, tables, pos0,
                                      k_scales=ks2r, v_scales=vs2r)
        out, k2, v2, ks2, vs2 = rp.ragged_paged_attention_arrays(
            q, kn, vn, kb, vb, tables, pos0, lens, slots,
            k_scales=ks, v_scales=vs)
        np.testing.assert_array_equal(np.asarray(k2), np.asarray(k2r))
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(v2r))
        np.testing.assert_array_equal(np.asarray(ks2), np.asarray(ks2r))
        np.testing.assert_array_equal(np.asarray(vs2), np.asarray(vs2r))
        for b in valid:
            # scale folding reassociates one multiply per element: not
            # bitwise vs dequantize-then-einsum, but tight
            np.testing.assert_allclose(
                np.asarray(out[b, :qlens[b]]),
                np.asarray(want[b, :qlens[b]]), rtol=3e-5, atol=3e-6,
                err_msg=f"{mix} row {b}")

    def test_scale_args_must_pair(self):
        q, kn, vn, tables, pos0, lens, slots, _, _, geo = _mix("single_row")
        nb, bs, H, D = geo
        kb = jnp.zeros((nb, bs, H * D), jnp.int8)
        with pytest.raises(ValueError, match="both k_scales and v_scales"):
            rp.ragged_paged_attention_arrays(
                q, kn, vn, kb, kb, tables, pos0, lens, slots,
                k_scales=jnp.zeros((nb, H), jnp.float32))


# ---------------------------------------------------------------------------
# kernel level: interpret mode, conforming geometry (fast tier)
# ---------------------------------------------------------------------------

@pytest.fixture
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")


def _kernel_case(quant, seed=0):
    """Mixed-length decode rows at kernel geometry (hd = 128): a
    mid-block row, an exactly-block-aligned row, and a padding (evicted)
    row."""
    rng = np.random.RandomState(seed)
    B, C, H, D = 3, 1, 2, 64
    bs = 32 if quant else 16
    nb, maxb = 8, 3
    tables = np.full((B, maxb), nb, np.int32)
    tables[0, :2] = [5, 2]
    tables[1, :3] = [1, 7, 3]
    lens = np.asarray([bs + 5, 3 * bs, 0], np.int32)
    pos0 = jnp.asarray(lens - 1, jnp.int32)
    q = jnp.asarray(rng.randn(B, C, H, D), jnp.float32)
    kn = jnp.asarray(rng.randn(B, C, H, D), jnp.float32)
    vn = jnp.asarray(rng.randn(B, C, H, D), jnp.float32)
    slots = np.full((B, C), nb * bs, np.int32)
    for b in range(2):
        p = int(lens[b]) - 1
        slots[b, 0] = int(tables[b][p // bs]) * bs + p % bs
    if quant:
        kb = jnp.asarray(rng.randint(-127, 128, (nb, bs, H * D)), jnp.int8)
        vb = jnp.asarray(rng.randint(-127, 128, (nb, bs, H * D)), jnp.int8)
        ks = jnp.asarray(rng.rand(nb, H) * 0.1, jnp.float32)
        vs = jnp.asarray(rng.rand(nb, H) * 0.1, jnp.float32)
    else:
        kb = jnp.asarray(rng.randn(nb, bs, H * D), jnp.float32)
        vb = jnp.asarray(rng.randn(nb, bs, H * D), jnp.float32)
        ks = vs = None
    return (q, kn, vn, kb, vb, jnp.asarray(tables), pos0,
            jnp.asarray(lens), jnp.asarray(slots), ks, vs)


def _equations(jaxpr):
    """Every equation of a jaxpr, those of its sub-jaxprs included (the
    kernel call is a `jit` of its own inside the caller's program)."""
    import jax

    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


class TestRaggedKernelInterpret:
    def test_fp_kernel_matches_reference(self, _interpret_mode):
        (q, kn, vn, kb, vb, tables, pos0, lens, slots,
         _, _) = _kernel_case(False)
        assert rp._ragged_kernel_ok(q, kb, 1, False)
        out, k2, v2 = rp.ragged_paged_attention_arrays(
            q, kn, vn, kb, vb, tables, pos0, lens, slots)
        k2r = paged_cache_update_arrays(kb, kn, slots)
        v2r = paged_cache_update_arrays(vb, vn, slots)
        want = paged_attention_arrays(q, k2r, v2r, tables, pos0)
        np.testing.assert_array_equal(np.asarray(k2), np.asarray(k2r))
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(v2r))
        # online softmax reorders reductions: last-ulp, not bitwise.  A
        # near-zero output is a sum of O(1) p·v terms that cancel, so its
        # last ulp sits at the terms' scale (fp32 eps 1.2e-7 × a few),
        # not at the result's — hence atol an order above eps
        np.testing.assert_allclose(np.asarray(out[:2]),
                                   np.asarray(want[:2]),
                                   rtol=1e-6, atol=1e-6)

    def test_int8_kernel_matches_reference(self, _interpret_mode):
        (q, kn, vn, kb, vb, tables, pos0, lens, slots,
         ks, vs) = _kernel_case(True)
        assert rp._ragged_kernel_ok(q, kb, 1, True)
        out, k2, v2, ks2, vs2 = rp.ragged_paged_attention_arrays(
            q, kn, vn, kb, vb, tables, pos0, lens, slots,
            k_scales=ks, v_scales=vs)
        k2r, ks2r = quantized_cache_update_arrays(kb, ks, kn, slots)
        v2r, vs2r = quantized_cache_update_arrays(vb, vs, vn, slots)
        want = paged_attention_arrays(q, k2r, v2r, tables, pos0,
                                      k_scales=ks2r, v_scales=vs2r)
        # the fused quantize/rescale write is the SAME arithmetic:
        # codes + scales land bit-identically
        np.testing.assert_array_equal(np.asarray(k2), np.asarray(k2r))
        np.testing.assert_array_equal(np.asarray(v2), np.asarray(v2r))
        np.testing.assert_array_equal(np.asarray(ks2), np.asarray(ks2r))
        np.testing.assert_array_equal(np.asarray(vs2), np.asarray(vs2r))
        np.testing.assert_allclose(np.asarray(out[:2]),
                                   np.asarray(want[:2]),
                                   rtol=3e-5, atol=3e-6)

    @pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
    def test_pools_pass_through_without_a_relayout(self, _interpret_mode,
                                                   quant):
        """The kernel call takes the pools as they are kept and hands
        them back the same: no `reshape`/`transpose` equation anywhere
        in the traced program touches an array of the pool's element
        count.  On a TPU each such reshape between ``[.., H, D]`` and
        ``[.., H*D]`` was a copy of the whole pool, twice per pool per
        layer per decode step."""
        import jax

        (q, kn, vn, kb, vb, tables, pos0, lens, slots,
         ks, vs) = _kernel_case(quant)
        assert kb.ndim == 3 and kb.size != q.size
        kw = dict(k_scales=ks, v_scales=vs) if quant else {}
        closed = jax.make_jaxpr(
            lambda *a: rp.ragged_paged_attention_arrays(*a, **kw))(
            q, kn, vn, kb, vb, tables, pos0, lens, slots)

        eqns = list(_equations(closed.jaxpr))
        assert any(e.primitive.name == "pallas_call" for e in eqns)
        relaid = [
            str(e) for e in eqns
            if e.primitive.name in ("reshape", "transpose")
            and any(getattr(v.aval, "size", 0) == kb.size
                    for v in list(e.invars) + list(e.outvars))]
        assert not relaid, relaid
        outs = closed.out_avals
        assert outs[1].shape == outs[2].shape == kb.shape
        assert outs[1].dtype == kb.dtype

    @pytest.mark.slow
    def test_scale_growth_steady_state_bit_stable(self, _interpret_mode):
        """A second, smaller write into the same block must leave the
        other codes bit-identical (factor exactly 1.0) — the kernel's
        rescale mirrors `quantized_cache_update_arrays`' monotonic-scale
        contract."""
        (q, kn, vn, kb, vb, tables, pos0, lens, slots,
         ks, vs) = _kernel_case(True, seed=3)
        out1 = rp.ragged_paged_attention_arrays(
            q, kn, vn, kb, vb, tables, pos0, lens, slots,
            k_scales=ks, v_scales=vs)
        _, k2, v2, ks2, vs2 = out1
        # next decode step: position advances by one, tiny new row
        lens2 = jnp.asarray(np.where(np.asarray(lens) > 0,
                                     np.asarray(lens) + 1, 0), jnp.int32)
        bs = kb.shape[1]
        nb = kb.shape[0]
        slots2 = np.full(np.asarray(slots).shape, nb * bs, np.int32)
        for b in range(2):
            p = int(lens2[b]) - 1
            slots2[b, 0] = int(tables[b][p // bs]) * bs + p % bs
        small = jnp.asarray(np.ones_like(np.asarray(kn)) * 1e-4)
        out2 = rp.ragged_paged_attention_arrays(
            q, small, small, k2, v2, tables, lens2 - 1, lens2,
            jnp.asarray(slots2), k_scales=ks2, v_scales=vs2)
        _, k3, v3, ks3, vs3 = out2
        k2r, ks2r = quantized_cache_update_arrays(k2, ks2, small,
                                                  jnp.asarray(slots2))
        np.testing.assert_array_equal(np.asarray(k3), np.asarray(k2r))
        np.testing.assert_array_equal(np.asarray(ks3), np.asarray(ks2r))

    def test_gate_counts_and_fallbacks(self, _interpret_mode, monkeypatch):
        from paddle_tpu.ops import pallas_ops as po

        monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
        po.reset_attention_path_counts()
        (q, kn, vn, kb, vb, *_rest) = _kernel_case(False)
        assert rp._ragged_kernel_ok(q, kb, 1, False)
        assert not rp._ragged_kernel_ok(q, kb, 4, False)     # chunk > 1
        bad_q = jnp.zeros((3, 1, 2, 8), jnp.float32)         # hd = 16
        assert not rp._ragged_kernel_ok(bad_q, kb, 1, False)
        odd = jnp.zeros((4, 12) + kb.shape[2:], kb.dtype)    # bs % 8 != 0
        assert not rp._ragged_kernel_ok(q, odd, 1, False)
        monkeypatch.setenv("PTPU_RAGGED_KERNEL", "0")
        assert not rp._ragged_kernel_ok(q, kb, 1, False)
        c = po.attention_path_counts()
        assert c.get("ragged_kernel") == 1
        assert c.get("ragged_fallback:chunk_gt_1") == 1
        assert c.get("ragged_fallback:head_geometry") == 1
        assert c.get("ragged_fallback:block_size") == 1
        assert c.get("ragged_fallback:disabled") == 1


# -- the per-head body over a 64-token tile (PR 29) ---------------------------

def _tile_case(lens, *, bs, hq=2, hkv=2, d=128, nb_spare=3, seed=0,
               window=None, dtype=np.float32):
    """Decode rows of the given lengths (0: a padding row, slot -1) over
    pools in which EVERY position no row can see is NaN: blocks no row
    owns, and - with `window` - the blocks wholly behind it, which the
    table names -1 as the window group leaves them.  Table entries past a
    row's end are -1.  A kernel that multiplies a buffer it did not fill,
    or a block it should not have fetched, by a zero weight puts NaN out."""
    rng = np.random.RandomState(seed)
    b = len(lens)
    maxb = max(-(-n // bs) for n in lens) + 2
    need = [-(-n // bs) for n in lens]
    nb = sum(need) + nb_spare
    perm = list(rng.permutation(nb))
    tables = np.full((b, maxb), -1, np.int32)
    slots = np.full((b, 1), -1, np.int32)
    kb = np.full((nb, bs, hkv * d), np.nan, dtype)
    vb = np.full((nb, bs, hkv * d), np.nan, dtype)
    for r, n in enumerate(lens):
        first = 0 if window is None else max(n - window, 0) // bs
        for j in range(first, need[r]):
            blk = tables[r, j] = perm.pop()
            kb[blk] = rng.randn(bs, hkv * d)
            vb[blk] = rng.randn(bs, hkv * d)
        if n:
            slots[r, 0] = tables[r, (n - 1) // bs] * bs + (n - 1) % bs
    f = lambda *s: jnp.asarray(rng.randn(*s).astype(dtype))      # noqa: E731
    lens = jnp.asarray(lens, jnp.int32)
    return (f(b, 1, hq, d), f(b, 1, hkv, d), f(b, 1, hkv, d),
            jnp.asarray(kb), jnp.asarray(vb), jnp.asarray(tables),
            jnp.maximum(lens - 1, 0), lens, jnp.asarray(slots))


def _against_fallback(args, window=None, tol=2e-6):
    """The kernel (interpret mode) against the XLA reference composition,
    row by row; padding rows put out zeros; the pools are written bit for
    bit as `paged_cache_update_arrays` writes them."""
    q, kn, vn, kb, vb, tables, pos0, lens, slots = args
    kw = {} if window is None else {"window": window}
    out, k2, v2 = rp.ragged_paged_attention_arrays(*args, **kw)
    k2r = paged_cache_update_arrays(kb, kn, slots)
    v2r = paged_cache_update_arrays(vb, vn, slots)
    for got, ref in ((k2, k2r), (v2, v2r)):     # (NaN == NaN as float32)
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(ref, np.float32))
    # the reference gathers every table entry: give it pools without the
    # NaN (it masks those positions; the kernel must never have met them)
    want = paged_attention_arrays(q, jnp.nan_to_num(k2r), jnp.nan_to_num(v2r),
                                  tables, pos0, **kw)
    out, want = np.asarray(out, np.float32), np.asarray(want, np.float32)
    for r, n in enumerate(np.asarray(lens)):
        if n:
            np.testing.assert_allclose(out[r], want[r], rtol=tol, atol=tol,
                                       err_msg=f"row {r}, length {n}")
        else:
            np.testing.assert_array_equal(out[r], 0.0)


# a row of every length around a block's and a tile's edge, a long one,
# and a padding row between them
TILE_LENS = [1, 15, 16, 17, 63, 0, 64, 65, 100, 448]


class TestHeadProductsOverTiles:
    @pytest.fixture(autouse=True)
    def _counted(self, _interpret_mode, monkeypatch):
        from paddle_tpu.ops import pallas_ops as po

        monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
        po.reset_attention_path_counts()
        self.counts = po.attention_path_counts

    @pytest.mark.parametrize("bs", [16, 32])
    def test_one_member_every_length(self, bs):
        """`g == 1`, d 128: lengths {1, 15, 16, 17, 63, 64, 65, 100, 448}
        and a padding row in one batch; entries past a row's end -1, every
        block no row owns NaN."""
        _against_fallback(_tile_case(TILE_LENS, bs=bs))
        assert self.counts().get("ragged_kernel:head_products") == 1
        assert "ragged_kernel:segment_products" not in self.counts()

    @pytest.mark.parametrize("length", [130, 146, 162, 178, 129, 192])
    def test_write_slot_in_each_block_of_a_tile(self, length):
        """The new token lands in the first, second, third and fourth
        block of the row's last tile (`bs` 16: tiles of 64 from 0), at a
        block's first position (129 = 128 + 1) and its last (192)."""
        _against_fallback(_tile_case([length, 64], bs=16, seed=length))

    @pytest.mark.parametrize("bs,window", [(16, 100), (16, 64), (32, 70),
                                           (16, 17)])
    def test_window_starts_inside_a_tile(self, bs, window):
        """`first_kb` of the window is not a multiple of the tile (length
        448, window 100, `bs` 16: block 21 of tiles of 4), the blocks
        behind it are -1 in the table and NaN in the pool."""
        lens = [448, 200, 65, 17, 0, 101]
        assert any(max(n - window, 0) // bs % (64 // bs) for n in lens)
        _against_fallback(_tile_case(lens, bs=bs, window=window),
                          window=window)

    @pytest.mark.parametrize("bs", [16, 32, 64])
    def test_grouped_heads_over_tiles(self, bs):
        """Six query heads over one K/V head (afmoe's group) at the block
        sizes a user may set for it."""
        _against_fallback(_tile_case([1, 63, 64, 65, 0, 300], bs=bs, hq=6,
                                     hkv=1, window=None))
        _against_fallback(_tile_case([1, 63, 64, 65, 0, 300], bs=bs, hq=6,
                                     hkv=1, window=128), window=128)
        assert self.counts().get("ragged_kernel:head_products") == 2

    def test_bfloat16_pools(self):
        """The served dtype: bf16 operands, float32 state - against the
        same reference within bf16's rounding of the probabilities."""
        _against_fallback(_tile_case(TILE_LENS, bs=16, hq=4, hkv=4,
                                     dtype=jnp.bfloat16), tol=2e-2)

    def test_evicted_row_streams_its_pool(self):
        """A live row whose write slot is out of range (evicted between
        schedule and run): nothing is written, and it attends over what
        the pool holds for its `length` positions."""
        args = list(_tile_case([70, 33], bs=16))
        slots = np.asarray(args[8]).copy()
        slots[0, 0] = args[3].shape[0] * 16          # out of range
        args[8] = jnp.asarray(slots)
        _against_fallback(tuple(args))

    @pytest.mark.parametrize("quant,d", [(False, 64), (True, 64),
                                         (True, 128)],
                             ids=["fp-d64", "int8-d64", "int8-d128"])
    def test_segment_body_kept(self, quant, d):
        """Heads of 64 lanes and the int8 pools keep the segment body;
        the choice reads head dim and pool dtype, nothing else."""
        q = jnp.zeros((2, 1, 2, d), jnp.float32)
        pool = jnp.zeros((4, 32, 2 * d), jnp.int8 if quant else jnp.float32)
        assert rp._ragged_kernel_ok(q, pool, 1, quant)
        assert self.counts().get("ragged_kernel:segment_products") == 1
        assert "ragged_kernel:head_products" not in self.counts()

    def test_tile_follows_block_size(self):
        """`_TILE_TOKENS // block_size` table entries a step: the stream
        buffers of the traced kernel are `[2, 1, 64, h*d]` at `bs` 16 and
        32, one block at 64 and beyond, and one block for the segment
        body."""
        import jax

        def stream_rows(bs, d=128, dtype=jnp.float32):
            args = _tile_case([5], bs=bs, d=d, dtype=dtype)
            jaxpr = jax.make_jaxpr(rp.ragged_paged_attention_arrays)(*args)
            [call] = [e for e in _equations(jaxpr.jaxpr)
                      if e.primitive.name == "pallas_call"]
            # scratch follows the inputs and outputs: K stream, V stream,
            # semaphores, the target block
            k_stream, v_stream = [v.aval.shape for v in
                                  call.params["jaxpr"].invars
                                  if len(v.aval.shape) == 4][:2]
            assert k_stream == v_stream and k_stream[:2] == (2, 1)
            return k_stream[2]

        assert rp._TILE_TOKENS == 64
        assert [stream_rows(bs) for bs in (16, 32, 64, 128)] == [
            64, 64, 64, 128]
        assert stream_rows(16, d=64) == 16


# ---------------------------------------------------------------------------
# engine level
# ---------------------------------------------------------------------------

def _dense_solo(model, prompt, **kw):
    from paddle_tpu.core.tensor import Tensor

    out = model.generate(Tensor(jnp.asarray(prompt[None])),
                         max_new_tokens=NEW, **kw)
    return np.asarray(out._data)[0]


class TestEngineRaggedParity:
    def test_five_kinds_of_program(self, model, prompts):
        """Whole-prompt prefill, its chunked continuation, decode with
        and without drafts, sampling: `prefill`, `ragged`, `verify` and
        `sample`; an engine that keeps a step in flight adds `feed`, its
        decode step's upload; and nothing else."""
        eng = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=2, max_num_batched_tokens=4,
            speculative_tokens=2))
        repeat = np.tile(prompts[0], 3)         # n-gram drafts hit
        eng.generate([repeat, prompts[0]], SamplingParams(max_new_tokens=4))
        assert {key[0] for key in eng._jit_cache} == {
            "prefill", "ragged", "verify", "sample"}
        eng = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=2, max_num_batched_tokens=4))
        eng.generate([repeat, prompts[0]], SamplingParams(max_new_tokens=4))
        assert {key[0] for key in eng._jit_cache} == {
            "prefill", "ragged", "feed", "sample"}

    @pytest.mark.slow
    def test_ragged_matches_dense(self, model, prompts):
        """fp32: the engine == each row's solo dense `generate()` token
        for token, greedy AND fixed-seed sampling, on a mixed-length
        batch.  (The full parity surface — staggered arrivals,
        preemption — is tests/test_serving.py.)"""
        kw = dict(do_sample=True, temperature=0.8, top_k=20, top_p=0.9)
        sps = [SamplingParams(max_new_tokens=NEW)] * 4 + [
            SamplingParams(max_new_tokens=NEW, seed=7 + i, **kw)
            for i in range(4, 8)]
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=8))
        outs = eng.generate(prompts, sps)
        for i, p in enumerate(prompts):
            dense = (_dense_solo(model, p) if i < 4
                     else _dense_solo(model, p, seed=7 + i, **kw))
            np.testing.assert_array_equal(dense, outs[i],
                                          err_msg=f"ragged vs dense {i}")
        assert eng.cache.blocks_in_use == 0

    @pytest.mark.slow
    def test_ragged_chunked_prefill_matches_whole(self, model, prompts):
        """The ragged(1, C) prefill-continuation program: chunked and
        unchunked ragged engines agree token for token.  (Slow tier:
        tests/test_serving.py's chunked-prefill test runs the ragged
        DEFAULT in the fast tier.)"""
        whole = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=1))
        chunked = LLMEngine(model, EngineConfig(
            block_size=16, max_num_seqs=1, max_num_batched_tokens=3))
        [a] = whole.generate([prompts[2]],
                             SamplingParams(max_new_tokens=NEW))
        [b] = chunked.generate([prompts[2]],
                               SamplingParams(max_new_tokens=NEW))
        np.testing.assert_array_equal(a, b)

    @pytest.mark.slow
    def test_int8_kv_ragged_parity(self, model, prompts):
        """int8 KV on the ragged path: ≥90% greedy token agreement vs the
        fp engine (the PR-4 documented tolerance), with the pools freed
        at the end.  Slow tier: the fast tier already pins this through
        tests/test_lowbit.py's engine suite, which runs the ragged
        DEFAULT (plus TestDequantPassEliminated here drives the int8
        ragged engine directly)."""
        fp = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=8))
        q8 = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=8,
                                           kv_cache_dtype="int8"))
        sp = SamplingParams(max_new_tokens=NEW)
        o_fp = fp.generate(prompts, sp)
        o_q8 = q8.generate(prompts, sp)
        agree = tot = 0
        for a, b, p in zip(o_fp, o_q8, prompts):
            agree += int((a[len(p):] == b[len(p):]).sum())
            tot += NEW
        assert agree / tot >= 0.9, (agree, tot)
        assert q8.cache.blocks_in_use == 0


class TestRecompileRegression:
    # slow tier (engine compiles ARE the measurement, ~8 s): the driver
    # tier-1 budget at HEAD is ~790 s of 870 s on this host, so the
    # compile-heavy acceptance pins ride the full tier
    @staticmethod
    def _total(counter):
        snap = counter.snapshot()
        return (sum(snap.values()) if isinstance(snap, dict)
                else float(snap))

    @staticmethod
    def _causes(kind):
        """Total serving:<kind> recompile-cause increments, by axis."""
        snap = monitor.counter("jit/recompile_cause").snapshot()
        if not isinstance(snap, dict):
            return {}
        out = {}
        for k, v in sorted(snap.items()):
            if f"fn=serving:{kind}" in k and v:
                axis = [p for p in k.split(",") if
                        p.startswith("axis=")][0][len("axis="):]
                out[axis] = out.get(axis, 0) + v
        return out

    def _drive(self, model, prompts):
        """Warm on a batch of 3, then cross the power-of-2 boundary with
        a batch of 5.  Returns (compiles during warm, compiles after the crossing),
        the jit/recompiles twins, the recompile-cause delta across the
        crossing (ISSUE 12's explainer), and the kernels_per_step gauge
        at both compositions."""
        monitor.enable(True)
        try:
            eng = LLMEngine(model, EngineConfig(
                block_size=16, max_num_seqs=8))
            sp = SamplingParams(max_new_tokens=2)
            kind = "ragged"
            jit_child = monitor.counter("jit/recompiles").labels(
                fn=f"serving:{kind}")
            kern = monitor.gauge("serving/kernels_per_step")
            # two distinct prompt LENGTHS only, both phases: any compile
            # delta is the decode/sampler programs, not prefill
            warm3 = [prompts[0], prompts[3], prompts[1]]    # lens 3,3,5
            cross5 = warm3 + [prompts[4], prompts[0]]       # lens +5,3
            eng.generate(warm3, sp)
            warm = self._total(eng._m_compiles)
            jit_warm = jit_child.value
            cause_warm = self._causes(kind)
            k_warm = kern.value
            eng.generate(cross5, sp)
            after = self._total(eng._m_compiles)
            jit_after = jit_child.value
            cause_delta = {
                a: v - cause_warm.get(a, 0)
                for a, v in self._causes(kind).items()
                if v != cause_warm.get(a, 0)}
            return (warm, after, jit_warm, jit_after, cause_delta,
                    k_warm, kern.value)
        finally:
            monitor.refresh()

    @pytest.mark.slow
    def test_bucket_crossing_flat_on_ragged(self, model, prompts):
        """ISSUE 8 acceptance, extended by ISSUE 12: ONE compiled decode
        program regardless of batch composition.  Crossing a power of
        two (3 → 5 running rows) adds ZERO compiles, leaves
        `jit/recompile_cause{fn=serving:ragged}` EMPTY, and keeps
        `serving/kernels_per_step` FLAT."""
        w, a, jw, ja, cause, k3, k5 = self._drive(model, prompts)
        assert a == w, (w, a)
        assert ja == jw, (jw, ja)
        assert cause == {}, cause           # nothing to explain
        assert k3 == k5 == 2.0, (k3, k5)    # decode program + sampler


class TestDequantPassEliminated:
    def _gather_count(self, snap):
        v = snap.get("lowbit/dequant_calls")
        if isinstance(v, dict):
            return sum(n for k, n in v.items() if "paged_gather" in k)
        return 0

    @pytest.mark.slow
    def test_no_paged_gather_dequant_on_ragged(self, model, prompts):
        """ISSUE 8 acceptance: the int8 ENGINE makes NO
        `lowbit/dequant_calls{site="paged_gather"}` increments (the
        dequant is folded into the attention program).  One short
        prompt: the counter ticks at TRACE time, so compiling the
        engine's programs once is the whole measurement."""
        monitor.enable(True)
        try:
            # the registry is process-global and cumulative: diff
            # around THIS engine's run (counting is at trace time,
            # and each fresh engine retraces its own programs)
            before = self._gather_count(monitor.snapshot())
            eng = LLMEngine(model, EngineConfig(
                block_size=16, max_num_seqs=2, kv_cache_dtype="int8"))
            eng.generate(prompts[:1], SamplingParams(max_new_tokens=2))
            after = self._gather_count(monitor.snapshot())
        finally:
            monitor.refresh()
        assert after == before, (before, after)

    def test_op_level_lowering_counts(self):
        """Same invariant at the op level, no engine: lowering the
        int8 ragged op traces zero paged_gather dequants; lowering the
        reference quantized attention traces them."""
        import jax

        (q, kn, vn, tables, pos0, lens, slots, _v, _q,
         geo) = _mix("all_decode")
        nb, bs, H, D = geo
        kb = jnp.zeros((nb, bs, H * D), jnp.int8)
        ks = jnp.zeros((nb, H), jnp.float32)
        monitor.enable(True)
        try:
            before = self._gather_count(monitor.snapshot())
            jax.jit(lambda *a: rp.ragged_paged_attention_arrays(
                *a, k_scales=ks, v_scales=ks)).lower(
                q, kn, vn, kb, kb, tables, pos0, lens, slots)
            mid = self._gather_count(monitor.snapshot())
            jax.jit(lambda *a: paged_attention_arrays(
                *a, k_scales=ks, v_scales=ks)).lower(
                q, kb, kb, tables, pos0)
            after = self._gather_count(monitor.snapshot())
        finally:
            monitor.refresh()
        assert mid - before == 0, (before, mid)
        assert after - mid > 0, (mid, after)


class TestMonitorWiring:
    @pytest.mark.slow
    def test_decode_breakdown_has_ragged_fused(self, model, prompts):
        # slow tier: the fast tier asserts the same surface through the
        # serve_smoke --perf subprocess (test_serving.py)
        from paddle_tpu.monitor import perf as mperf

        mperf.enable(True)
        monitor.enable(True)
        try:
            eng = LLMEngine(model, EngineConfig(
                block_size=16, max_num_seqs=2))
            eng.generate(prompts[:1], SamplingParams(max_new_tokens=2))
            bd = eng.decode_breakdown(reps=1)
        finally:
            mperf.refresh()
            monitor.refresh()
            mperf.reset()
        assert "ragged_fused" in bd
        assert bd["ragged_fused"]["wall_time_s"] > 0
        # the before-side trio stays in the same report
        for name in ("block_gather", "attention", "cache_update", "step"):
            assert name in bd, sorted(bd)
