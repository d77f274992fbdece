"""Masked / cross-attention flash kernel parity tests.

Runs the real Pallas kernels in interpret mode (PTPU_PALLAS_INTERPRET=1)
on the CPU test mesh, against mha_reference — reference analog:
test_flash_attention.py parity vs the naive softmax path.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops import pallas_ops as po


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    monkeypatch.setenv("PTPU_PALLAS_INTERPRET", "1")


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32) * 0.5


def _parity(q, k, v, mask=None, is_causal=False, rtol=2e-4, atol=2e-4,
            kv_lens=None, segment_ids=None):
    assert po._pallas_ok(q, k, is_causal, mask, kv_lens, segment_ids)
    out = po.flash_attention_arrays(q, k, v, mask, is_causal,
                                    kv_lens=kv_lens,
                                    segment_ids=segment_ids)
    ref = po.mha_reference(q, k, v, mask, is_causal, kv_lens=kv_lens,
                           segment_ids=segment_ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=rtol, atol=atol)

    def loss_flash(q, k, v):
        return jnp.sum(po.flash_attention_arrays(
            q, k, v, mask, is_causal, kv_lens=kv_lens,
            segment_ids=segment_ids) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(po.mha_reference(
            q, k, v, mask, is_causal, kv_lens=kv_lens,
            segment_ids=segment_ids) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-4)


def test_padding_mask_batch_shared():
    """[B, 1, S, S] additive padding mask (the padded-batch shape that
    previously fell off the flash path)."""
    B, S, H, D = 2, 256, 2, 64
    q, k, v = _rand((B, S, H, D), 0), _rand((B, S, H, D), 1), _rand((B, S, H, D), 2)
    # keys beyond per-row length are masked out
    lengths = jnp.asarray([200, 131])
    key_ok = jnp.arange(S)[None, :] < lengths[:, None]          # [B, S]
    mask = jnp.where(key_ok, 0.0, -1e30)[:, None, None, :]       # [B,1,1,S]
    mask = jnp.broadcast_to(mask, (B, 1, S, S))
    _parity(q, k, v, mask=mask, is_causal=False)


def test_padding_mask_with_causal():
    B, S, H, D = 2, 256, 2, 64
    q, k, v = _rand((B, S, H, D), 3), _rand((B, S, H, D), 4), _rand((B, S, H, D), 5)
    key_ok = jnp.arange(S)[None, :] < jnp.asarray([256, 100])[:, None]
    mask = jnp.broadcast_to(
        jnp.where(key_ok, 0.0, -1e30)[:, None, None, :], (B, 1, S, S))
    _parity(q, k, v, mask=mask, is_causal=True)


def test_per_head_bool_mask():
    B, S, H, D = 1, 256, 3, 64
    q, k, v = _rand((B, S, H, D), 6), _rand((B, S, H, D), 7), _rand((B, S, H, D), 8)
    keep = np.random.RandomState(9).rand(B, H, S, S) > 0.3
    # every query row must keep at least one key (else softmax is undefined)
    keep[..., 0] = True
    _parity(q, k, v, mask=jnp.asarray(keep), is_causal=False, rtol=1e-3)


def test_cross_attention_different_lengths():
    """sq != sk non-causal (cross attention) now takes the kernel path."""
    B, H, D = 2, 2, 64
    q = _rand((B, 256, H, D), 10)
    k = _rand((B, 512, H, D), 11)
    v = _rand((B, 512, H, D), 12)
    _parity(q, k, v, is_causal=False)


def test_2d_mask_promoted():
    B, S, H, D = 1, 256, 1, 64
    q, k, v = _rand((B, S, H, D), 13), _rand((B, S, H, D), 14), _rand((B, S, H, D), 15)
    mask = jnp.where(
        jnp.asarray(np.random.RandomState(16).rand(S, S) > 0.2), 0.0, -1e30)
    _parity(q, k, v, mask=mask, is_causal=False, rtol=1e-3)


def test_gating_still_rejects_bad_shapes():
    B, S, H, D = 1, 256, 2, 64
    q = _rand((B, S, H, D), 17)
    k = _rand((B, S, H, D), 18)
    # mask with wrong trailing dims -> no kernel path
    bad = jnp.zeros((B, 1, S, S + 1))
    assert not po._pallas_ok(q, k, False, bad)
    # causal cross-attention with sq < sk now RIDES the kernel path
    k2 = _rand((B, 512, H, D), 19)
    assert po._pallas_ok(q, k2, True, None)
    # ...but more queries than keys has no standard causal alignment
    assert not po._pallas_ok(k2, q, True, None)
    # indivisible sequence falls back
    q3 = _rand((B, 250, H, D), 20)
    assert not po._pallas_ok(q3, q3, False, None)


def test_causal_cross_attention_parity():
    """Causal sq != sk (end-aligned diagonal, the decode-chunk /
    speculative shape): kernel vs reference, values and grads."""
    B, H, D = 2, 2, 64
    q = _rand((B, 256, H, D), 30)
    k = _rand((B, 512, H, D), 31)
    v = _rand((B, 512, H, D), 32)
    _parity(q, k, v, is_causal=True)


def test_kv_lens_variable_length_parity():
    """Right-padded batch via kv_lens keeps the kernel with no [B,H,S,S]
    mask in HBM (VERDICT r2 weak #6)."""
    B, S, H, D = 2, 256, 2, 64
    q, k, v = _rand((B, S, H, D), 33), _rand((B, S, H, D), 34), _rand(
        (B, S, H, D), 35)
    lens = jnp.asarray([200, 131], jnp.int32)
    _parity(q, k, v, is_causal=False, kv_lens=lens)
    _parity(q, k, v, is_causal=True, kv_lens=lens)


def test_kv_lens_matches_equivalent_mask():
    B, S, H, D = 2, 256, 2, 64
    q, k, v = _rand((B, S, H, D), 36), _rand((B, S, H, D), 37), _rand(
        (B, S, H, D), 38)
    lens = jnp.asarray([96, 256], jnp.int32)
    out_lens = po.flash_attention_arrays(q, k, v, None, False, kv_lens=lens)
    key_ok = jnp.arange(S)[None, :] < lens[:, None]
    mask = jnp.broadcast_to(
        jnp.where(key_ok, 0.0, -1e30)[:, None, None, :], (B, 1, S, S))
    out_mask = po.flash_attention_arrays(q, k, v, mask, False)
    np.testing.assert_allclose(np.asarray(out_lens), np.asarray(out_mask),
                               rtol=2e-4, atol=2e-4)


def test_path_counters(monkeypatch):
    """Flag-gated gate-decision counters (VERDICT r2 weak #7)."""
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    B, S, H, D = 1, 256, 2, 64
    q, k, v = _rand((B, S, H, D), 40), _rand((B, S, H, D), 41), _rand(
        (B, S, H, D), 42)
    po.flash_attention_arrays(q, k, v, None, True)
    q_odd = _rand((B, 250, H, D), 43)
    po.flash_attention_arrays(q_odd, q_odd, q_odd, None, False)
    counts = po.attention_path_counts()
    assert counts.get("attn_kernel", 0) >= 1
    assert counts.get("attn_fallback:seq_not_128_multiple", 0) >= 1
    po.reset_attention_path_counts()
    assert po.attention_path_counts() == {}


def test_flash_decode_matches_masked_reference(monkeypatch):
    """Pallas decode kernel (valid-prefix DMA reads + online softmax) vs the
    full-cache masked-softmax XLA path. Forced on: the auto policy keeps
    short caches on the XLA path (kernel fixed costs dominate there)."""
    monkeypatch.setenv("PTPU_FLASH_DECODE", "1")
    from paddle_tpu.ops.pallas_ops import (cached_attention_arrays,
                                           flash_decode_arrays)

    rs = np.random.RandomState(11)
    b, h, d, s_max = 2, 4, 64, 256
    q = jnp.asarray(rs.randn(b, 1, h, d), jnp.float32)
    kc = jnp.asarray(rs.randn(b, s_max, h, d), jnp.float32)
    vc = jnp.asarray(rs.randn(b, s_max, h, d), jnp.float32)
    assert po._decode_ok(q, kc, vc)
    for t in (0, 1, 127, 128, 200, 255):
        out = flash_decode_arrays(q, kc, vc, jnp.int32(t + 1))
        # reference: masked softmax over the full cache
        scale = 1.0 / np.sqrt(d)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc) * scale
        keep = (jnp.arange(s_max) <= t)[None, None, None, :]
        logits = jnp.where(keep, logits, -1e30)
        probs = jax.nn.softmax(logits, -1)
        ref = jnp.einsum("bhqk,bkhd->bqhd", probs, vc)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5, err_msg=f"t={t}")


def test_cached_attention_routes_to_decode_kernel(monkeypatch):
    """cached_attention_arrays S_q=1 path uses the kernel (forced — auto
    policy keeps short caches on XLA) and still returns the updated
    caches; parity against the XLA path shapes/values."""
    monkeypatch.setenv("PTPU_FLASH_DECODE", "1")
    from paddle_tpu.ops import pallas_ops as po

    rs = np.random.RandomState(12)
    b, h, d, s_max = 1, 2, 64, 128
    kc = jnp.zeros((b, s_max, h, d), jnp.float32)
    vc = jnp.zeros((b, s_max, h, d), jnp.float32)
    # prefill 3 tokens one at a time through the cached path, compare with
    # growing full attention
    toks = jnp.asarray(rs.randn(b, 3, h, d), jnp.float32)
    assert po._decode_ok(toks[:, :1], kc, vc)   # the kernel path IS taken
    outs = []
    for t in range(3):
        q = k = v = toks[:, t:t + 1]
        o, kc, vc = po.cached_attention_arrays(q, k, v, kc, vc, t)
        outs.append(o)
    # full causal attention over the 3 tokens
    full = po.mha_reference(toks, toks, toks, is_causal=True)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_kernel_vs_reference_shapes():
    """Numeric check of the flash-decode kernel (interpret mode) against
    masked full attention over the valid cache prefix, across batch-slab /
    block_k boundary shapes (ragged final block, single-block, tiny len)."""
    from paddle_tpu.ops.pallas_ops import flash_decode_arrays, mha_reference

    rng = np.random.RandomState(0)
    for (B, S_MAX, H, D, length) in [(2, 128, 4, 64, 37),
                                     (4, 256, 12, 64, 200),
                                     (2, 128, 2, 64, 128),
                                     (3, 384, 4, 32, 5)]:
        q = jnp.asarray(rng.randn(B, 1, H, D), jnp.float32)
        kc = jnp.asarray(rng.randn(B, S_MAX, H * D), jnp.float32)
        vc = jnp.asarray(rng.randn(B, S_MAX, H * D), jnp.float32)
        out = flash_decode_arrays(q, kc, vc, jnp.int32(length))
        ref = mha_reference(q, kc[:, :length].reshape(B, length, H, D),
                            vc[:, :length].reshape(B, length, H, D))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=1e-4, atol=1e-4)


def test_decode_auto_policy_smax_threshold(monkeypatch):
    """Auto path selection: short caches stay on XLA (fixed-cost regime),
    long caches take the prefix-skipping kernel; env forces override."""
    from paddle_tpu.ops import pallas_ops as po2

    rs = np.random.RandomState(13)
    q = jnp.asarray(rs.randn(1, 1, 2, 64), jnp.float32)

    def caches(smax):
        return (jnp.zeros((1, smax, 128), jnp.float32),
                jnp.zeros((1, smax, 128), jnp.float32))

    monkeypatch.delenv("PTPU_FLASH_DECODE", raising=False)
    kc, vc = caches(256)
    assert not po2._decode_ok(q, kc, vc)          # short: XLA
    kc, vc = caches(2048)
    assert po2._decode_ok(q, kc, vc)              # long: kernel
    monkeypatch.setenv("PTPU_FLASH_DECODE", "1")
    kc, vc = caches(256)
    assert po2._decode_ok(q, kc, vc)              # forced on
    monkeypatch.setenv("PTPU_FLASH_DECODE", "0")
    kc, vc = caches(2048)
    assert not po2._decode_ok(q, kc, vc)          # forced off


# ---------------------------------------------------------------------------
# Packed-sequence (segment-id) attention — VERDICT r3 item 8
# ---------------------------------------------------------------------------

def _seg_ids(lengths, S):
    """Packed segment ids: e.g. [3, 2] with S=8 -> [0,0,0,1,1,2,2,2]
    (the remainder is one final segment)."""
    ids = np.zeros(S, np.int32)
    pos = 0
    for i, ln in enumerate(lengths):
        ids[pos:pos + ln] = i
        pos += ln
    ids[pos:] = len(lengths)
    return ids


def _seg_parity(q, k, v, segs, is_causal, rtol=2e-4, atol=2e-4):
    _parity(q, k, v, None, is_causal, rtol, atol, segment_ids=segs)


def test_segment_ids_packed_parity():
    """Multiple documents per row (the packed pretraining input format):
    kernel matches the dense segment-masked reference, fwd + grads."""
    B, S, H, D = 2, 256, 2, 64
    q, k, v = _rand((B, S, H, D), 10), _rand((B, S, H, D), 11), _rand((B, S, H, D), 12)
    segs = jnp.asarray(np.stack([_seg_ids([100, 80], S),
                                 _seg_ids([256], S)[:S]]), jnp.int32)
    _seg_parity(q, k, v, segs, is_causal=False)


def test_segment_ids_with_causal():
    B, S, H, D = 2, 256, 2, 64
    q, k, v = _rand((B, S, H, D), 13), _rand((B, S, H, D), 14), _rand((B, S, H, D), 15)
    segs = jnp.asarray(np.stack([_seg_ids([60, 60, 70], S),
                                 _seg_ids([128, 64], S)]), jnp.int32)
    _seg_parity(q, k, v, segs, is_causal=True)


def test_segment_ids_many_short_docs():
    """Segment boundaries landing inside and across kernel blocks."""
    B, S, H, D = 1, 384, 2, 64
    q, k, v = _rand((B, S, H, D), 16), _rand((B, S, H, D), 17), _rand((B, S, H, D), 18)
    segs = jnp.asarray(_seg_ids([50, 30, 77, 100, 64], S)[None], jnp.int32)
    _seg_parity(q, k, v, segs, is_causal=True)


def test_segment_path_counter_and_fallback(monkeypatch):
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    B, S, H, D = 1, 256, 2, 64
    q, k, v = _rand((B, S, H, D), 19), _rand((B, S, H, D), 20), _rand((B, S, H, D), 21)
    segs = jnp.asarray(_seg_ids([128, 128], S)[None], jnp.int32)
    po.flash_attention_arrays(q, k, v, None, True, segment_ids=segs)
    assert po.attention_path_counts().get("attn_kernel:segs") == 1
    # wrong shape raises clearly (no dense fallback can serve it either)
    bad = segs[:, :128]
    with pytest.raises(ValueError, match="segment_ids must be"):
        po.flash_attention_arrays(q, k, v, None, False, segment_ids=bad)


def test_segment_ids_compose_with_kv_lens():
    """Padding expressed as kv_lens composes with in-row packing: the
    kernel result on valid rows matches the dense reference."""
    B, S, H, D = 2, 256, 2, 64
    q, k, v = _rand((B, S, H, D), 22), _rand((B, S, H, D), 23), _rand((B, S, H, D), 24)
    segs = jnp.asarray(np.stack([_seg_ids([100, 100], S),
                                 _seg_ids([200], S)]), jnp.int32)
    lens = jnp.asarray([200, 256], jnp.int32)
    out = po.flash_attention_arrays(q, k, v, None, True, kv_lens=lens,
                                    segment_ids=segs)
    ref = po.mha_reference(q, k, v, None, True, kv_lens=lens,
                           segment_ids=segs)
    # compare only rows before each kv_len (padded-q rows are unspecified)
    for b, ln in enumerate([200, 256]):
        np.testing.assert_allclose(np.asarray(out)[b, :ln],
                                   np.asarray(ref)[b, :ln],
                                   rtol=2e-4, atol=2e-4)
