"""Pallas TPU kernels for the hot paths.

TPU-native replacement for the reference's hand-fused CUDA ops
(paddle/fluid/operators/fused/fused_attention_op.cu,
fused_multi_transformer_op.cu — which are full-sequence, non-flash;
SURVEY §5.7): here attention is blockwise/flash-style, O(seq) memory,
written for the MXU (block sizes multiples of 128 lanes) with an XLA
fallback used off-TPU and for odd shapes.

Layout convention: [batch, seq, num_heads, head_dim] (the reference's
fused-attention layout).
"""
from __future__ import annotations

import functools
import math

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core.dispatch import apply
from ..core import random as _rng

__all__ = [
    "flash_attention", "flash_attention_arrays", "mha_reference",
    "cached_attention_arrays", "attention_path_counts",
    "reset_attention_path_counts",
]

_NEG_INF = -1e30

# ---------------------------------------------------------------------------
# Path-taken debug counters (VERDICT r2 weak #6/#7): the kernel gates fall
# back silently by design; under PTPU_ATTN_DEBUG=1 every gate decision is
# counted so perf cliffs (serving shapes dropping to the O(S^2) path) are
# observable. Counting happens at TRACE time — each compiled program counts
# once per distinct shape, which is exactly the signal wanted.
# ---------------------------------------------------------------------------

import collections as _collections
import os as _os

_PATH_COUNTS: "_collections.Counter[str]" = _collections.Counter()


def _count_path(name):
    if _os.environ.get("PTPU_ATTN_DEBUG") == "1":
        _PATH_COUNTS[name] += 1


def attention_path_counts():
    """{path_name: times_traced} — populated under PTPU_ATTN_DEBUG=1."""
    return dict(_PATH_COUNTS)


def reset_attention_path_counts():
    _PATH_COUNTS.clear()


def _on_tpu() -> bool:
    # a backend that fails to initialise raises here: a kernel gate must
    # never read a dead chip as "not on TPU" and pick the XLA path
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# Reference (XLA) attention — also the source of the backward pass
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, mask=None, is_causal=False, scale=None,
                  kv_lens=None, segment_ids=None, window=None):
    """q,k,v: [B,S,H,D] → [B,S,H,D]. Computed in fp32 accumulation.
    kv_lens: optional [B] int32 valid key lengths (right-padded batch).
    segment_ids: optional [B, S] int32 packed-sequence ids (self-attention
    only): position pairs attend iff their ids match.
    k, v may hold fewer heads than q (grouped heads: query head h reads
    K/V head h // ratio).  window: with is_causal, a key is visible iff it
    lies fewer than `window` positions behind the query."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if k.shape[2] != q.shape[2]:
        if q.shape[2] % k.shape[2]:
            raise ValueError(
                f"{q.shape[2]} query heads are not a multiple of the K/V "
                f"heads ({k.shape[2]})")
        ratio = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, ratio, axis=2), jnp.repeat(v, ratio, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    logits = logits * scale
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        causal = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        if window is not None:
            causal &= ~jnp.tril(jnp.ones((sq, sk), bool), sk - sq - window)
        logits = jnp.where(causal, logits, _NEG_INF)
    if kv_lens is not None:
        k_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
        valid = k_pos[None, None, None, :] < jnp.asarray(
            kv_lens, jnp.int32)[:, None, None, None]
        logits = jnp.where(valid, logits, _NEG_INF)
    if segment_ids is not None:
        ids = jnp.asarray(segment_ids, jnp.int32)
        same = ids[:, None, :, None] == ids[:, None, None, :]   # [B,1,Sq,Sk]
        logits = jnp.where(same, logits, _NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, _NEG_INF)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


# ---------------------------------------------------------------------------
# Pallas flash forward
# ---------------------------------------------------------------------------

def _dot_f32(a, b, transpose_b=False):
    """Matmul keeping operand dtype with fp32 accumulation. bf16 operands
    ride the MXU's fast path (fp32 operands would run ~8x slower on v5e);
    fp32 operands pin HIGHEST precision so the correctness dtype doesn't
    silently truncate to bf16 inside the kernel."""
    dims = (((1,), (1 if transpose_b else 0,)), ((), ()))
    prec = (jax.lax.Precision.HIGHEST
            if a.dtype == jnp.float32 else jax.lax.Precision.DEFAULT)
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32,
                               precision=prec)


def _seg_kb_bounds(seg_vec, lo, hi, seq_len, block):
    """Block range [first, last) of positions in `seg_vec` ([seq_len]
    int32) whose id lies in [lo, hi] — packed-segment block skipping.
    Conservative-correct for ANY id layout: every exact match is inside
    the min/max positional envelope; non-matching positions inside it are
    killed by the in-tile equality mask."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (1, seq_len), 1)[0]
    valid = (seg_vec >= lo) & (seg_vec <= hi)
    first_pos = jnp.min(jnp.where(valid, iota, seq_len))
    last_pos = jnp.max(jnp.where(valid, iota, -1))
    return first_pos // block, (last_pos // block) + 1


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *refs, block_k, seq_k,
                      scale, causal, block_q, has_mask, has_lens,
                      has_segs=False, causal_offset=0, window=None):
    from jax.experimental import pallas as pl

    refs = list(refs)
    lens_ref = refs.pop(0) if has_lens else None
    mask_ref = refs.pop(0) if has_mask else None
    qseg_ref = refs.pop(0) if has_segs else None
    kseg_ref = refs.pop(0) if has_segs else None
    o_ref, lse_ref = refs
    qi = pl.program_id(2)
    q = q_ref[0, :, :]                              # [block_q, d], input dtype
    kv_len = lens_ref[0, 0] if has_lens else None
    q_seg = qseg_ref[0, :] if has_segs else None    # [block_q] int32

    m = jnp.full((block_q,), _NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc = jnp.zeros((block_q, q.shape[-1]), jnp.float32)

    num_kb = seq_k // block_k

    def body(kb, carry):
        m, l, acc = carry
        k = k_ref[0, pl.dslice(kb * block_k, block_k), :]
        v = v_ref[0, pl.dslice(kb * block_k, block_k), :]
        s = _dot_f32(q, k, transpose_b=True) * scale   # [bq, bk] fp32
        if has_mask:
            s = s + mask_ref[0, 0, :, pl.dslice(kb * block_k, block_k)
                             ].astype(jnp.float32)
        if causal or has_lens:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        if causal:
            # cross-attention (sq != sk) aligns causally at the END:
            # query row i attends keys <= i + (sk - sq)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos + causal_offset >= k_pos, s, _NEG_INF)
            if window is not None:
                s = jnp.where(q_pos + causal_offset - k_pos < window, s,
                              _NEG_INF)
        if has_lens:
            s = jnp.where(k_pos < kv_len, s, _NEG_INF)
        if has_segs:
            k_seg = kseg_ref[0, pl.dslice(kb * block_k, block_k)]
            s = jnp.where(q_seg[:, None] == k_seg[None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + _dot_f32(p.astype(v.dtype), v)
        return m_new, l_new, acc_new

    first_kb = 0
    if causal:
        # only key blocks up to (and including) the diagonal contribute
        last_kb = jnp.minimum(
            ((qi + 1) * block_q + causal_offset + block_k - 1) // block_k,
            num_kb)
        if window is not None:
            # key blocks wholly behind the window of this block's FIRST
            # query are behind every query's: skipped like the segment
            # envelope below, the in-tile mask kills the rest
            first_kb = jnp.maximum(
                qi * block_q + causal_offset - window + 1, 0) // block_k
    else:
        last_kb = num_kb
    if has_lens:
        # padded keys past kv_len never contribute — skip their blocks
        last_kb = jnp.minimum(last_kb, (kv_len + block_k - 1) // block_k)
    if has_segs:
        # packed segments: only key blocks overlapping this q block's
        # segment-id envelope contribute
        seg_first, seg_last = _seg_kb_bounds(
            kseg_ref[0, :], jnp.min(q_seg), jnp.max(q_seg), seq_k, block_k)
        first_kb = jnp.maximum(first_kb, seg_first)
        last_kb = jnp.minimum(last_kb, seg_last)
    m, l, acc = jax.lax.fori_loop(first_kb, last_kb, body, (m, l, acc))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0, :, :] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # logsumexp per row — the backward kernels rebuild p = exp(s - lse).
    # lse lives as [BH, 1, S]; each program writes its q-block slice.
    lse_ref[0, 0, pl.dslice(qi * block_q, block_q)] = m + jnp.log(l_safe)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *refs, block_k, seq_k, scale, causal, block_q,
                         has_mask, has_lens, has_segs=False,
                         causal_offset=0):
    from jax.experimental import pallas as pl

    refs = list(refs)
    lens_ref = refs.pop(0) if has_lens else None
    mask_ref = refs.pop(0) if has_mask else None
    qseg_ref = refs.pop(0) if has_segs else None
    kseg_ref = refs.pop(0) if has_segs else None
    (dq_ref,) = refs
    qi = pl.program_id(2)
    q = q_ref[0, :, :]                            # [bq, d]
    do = do_ref[0, :, :]                          # [bq, d]
    lse = lse_ref[0, 0, pl.dslice(qi * block_q, block_q)]   # [bq]
    delta = delta_ref[0, 0, pl.dslice(qi * block_q, block_q)]
    kv_len = lens_ref[0, 0] if has_lens else None
    q_seg = qseg_ref[0, :] if has_segs else None
    num_kb = seq_k // block_k

    def body(kb, dq):
        k = k_ref[0, pl.dslice(kb * block_k, block_k), :]
        v = v_ref[0, pl.dslice(kb * block_k, block_k), :]
        s = _dot_f32(q, k, transpose_b=True) * scale
        if has_mask:
            s = s + mask_ref[0, 0, :, pl.dslice(kb * block_k, block_k)
                             ].astype(jnp.float32)
        if causal or has_lens:
            k_pos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos + causal_offset >= k_pos, s, _NEG_INF)
        if has_lens:
            s = jnp.where(k_pos < kv_len, s, _NEG_INF)
        if has_segs:
            k_seg = kseg_ref[0, pl.dslice(kb * block_k, block_k)]
            s = jnp.where(q_seg[:, None] == k_seg[None, :], s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = _dot_f32(do, v, transpose_b=True)
        ds = p * (dp - delta[:, None])
        return dq + _dot_f32(ds.astype(k.dtype), k)

    first_kb = 0
    if causal:
        last_kb = jnp.minimum(
            ((qi + 1) * block_q + causal_offset + block_k - 1) // block_k,
            num_kb)
    else:
        last_kb = num_kb
    if has_lens:
        last_kb = jnp.minimum(last_kb, (kv_len + block_k - 1) // block_k)
    if has_segs:
        seg_first, seg_last = _seg_kb_bounds(
            kseg_ref[0, :], jnp.min(q_seg), jnp.max(q_seg), seq_k, block_k)
        first_kb = jnp.maximum(first_kb, seg_first)
        last_kb = jnp.minimum(last_kb, seg_last)
    dq = jnp.zeros((block_q, q.shape[-1]), jnp.float32)
    dq = jax.lax.fori_loop(first_kb, last_kb, body, dq)
    dq_ref[0, :, :] = (dq * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          *refs, block_q, seq_q, scale, causal, block_k,
                          has_mask, has_lens, has_segs=False,
                          causal_offset=0):
    from jax.experimental import pallas as pl

    refs = list(refs)
    lens_ref = refs.pop(0) if has_lens else None
    mask_ref = refs.pop(0) if has_mask else None
    qseg_ref = refs.pop(0) if has_segs else None   # [1, sq] full row
    kseg_ref = refs.pop(0) if has_segs else None   # [1, block_k] block
    dk_ref, dv_ref = refs
    ki = pl.program_id(2)
    k = k_ref[0, :, :]                            # [bk, d]
    v = v_ref[0, :, :]
    kv_len = lens_ref[0, 0] if has_lens else None
    k_seg = kseg_ref[0, :] if has_segs else None  # [bk]
    num_qb = seq_q // block_q

    def body(qb, carry):
        dk, dv = carry
        q = q_ref[0, pl.dslice(qb * block_q, block_q), :]
        do = do_ref[0, pl.dslice(qb * block_q, block_q), :]
        lse = lse_ref[0, 0, pl.dslice(qb * block_q, block_q)]
        delta = delta_ref[0, 0, pl.dslice(qb * block_q, block_q)]
        s = _dot_f32(q, k, transpose_b=True) * scale   # [bq, bk]
        if has_mask:
            # mask block: [sq, block_k] column slice, sliced by q rows
            s = s + mask_ref[0, 0, pl.dslice(qb * block_q, block_q), :
                             ].astype(jnp.float32)
        if causal or has_lens:
            k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        if causal:
            q_pos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            s = jnp.where(q_pos + causal_offset >= k_pos, s, _NEG_INF)
        if has_lens:
            s = jnp.where(k_pos < kv_len, s, _NEG_INF)
        if has_segs:
            q_seg = qseg_ref[0, pl.dslice(qb * block_q, block_q)]
            s = jnp.where(q_seg[:, None] == k_seg[None, :], s, _NEG_INF)
        p = jnp.exp(s - lse[:, None])
        pb = p.astype(do.dtype)
        dv = dv + _dot_f32(pb.T, do)
        dp = _dot_f32(do, v, transpose_b=True)
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dk = dk + _dot_f32(ds.T, q)
        return dk, dv

    # causal: only q blocks at/after this k block's diagonal contribute
    if causal:
        first_qb = jnp.maximum(ki * block_k - causal_offset, 0) // block_q
    else:
        first_qb = 0
    last_qb = num_qb
    if has_segs:
        seg_first, seg_last = _seg_kb_bounds(
            qseg_ref[0, :], jnp.min(k_seg), jnp.max(k_seg), seq_q, block_q)
        first_qb = jnp.maximum(first_qb, seg_first)
        last_qb = jnp.minimum(last_qb, seg_last)
    dk = jnp.zeros((k.shape[0], k.shape[1]), jnp.float32)
    dv = jnp.zeros_like(dk)
    dk, dv = jax.lax.fori_loop(first_qb, last_qb, body, (dk, dv))
    dk_ref[0, :, :] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0, :, :] = dv.astype(dv_ref.dtype)


def _largest_dividing_block(n, preferred=256, minimum=128):
    for b in (preferred, minimum):
        if n % b == 0:
            return min(b, n)
    return None


def _block_candidates(sq, sk):
    """Dividing (block_q, block_k) candidates, measured-best first.

    (512, 512) leads: on v5e at seq 1024 / d 64 it beat 256/256 by 15%
    and both XLA attention and the shipped jax flash kernel by ~2x
    (round-2 on-chip sweep); smaller geometries serve shorter sequences.
    """
    cands = []
    for bq, bk in ((512, 512), (1024, 1024), (512, 256), (256, 256),
                   (256, 128), (128, 128)):
        if sq % bq == 0 and sk % bk == 0 and (bq, bk) not in cands:
            cands.append((bq, bk))
    return cands or [(_largest_dividing_block(sq),
                      _largest_dividing_block(sk))]


# candidates are timed as an 8-deep chained jit so per-dispatch overhead
# amortizes out of the signal
_TUNE_CHAIN = 8


def _run_fwd_candidate(bh, sq, sk, d, dtype, is_causal, scale, bq, bk):
    k = jnp.zeros((bh, sk, d), dtype)
    v = jnp.zeros((bh, sk, d), dtype)

    @jax.jit
    def chain(q):
        def body(q, _):
            o, _lse = _flash_fwd(q, k, v, is_causal, scale,
                                 block_q=bq, block_k=bk)
            return o, None
        out, _ = jax.lax.scan(body, q, length=_TUNE_CHAIN)
        return out

    return chain(jnp.zeros((bh, sq, d), dtype))


def _run_bwd_candidate(bh, sq, sk, d, dtype, is_causal, scale, bq, bk):
    k = jnp.zeros((bh, sk, d), dtype)
    v = jnp.zeros((bh, sk, d), dtype)
    out = jnp.zeros((bh, sq, d), dtype)
    lse = jnp.zeros((bh, 1, sq), jnp.float32)
    do = jnp.zeros((bh, sq, d), dtype)

    @jax.jit
    def chain(q):
        def body(q, _):
            dq, _dk, _dv = _flash_bwd(q, k, v, out, lse, do, is_causal,
                                      scale, block_q=bq, block_k=bk)
            return dq, None
        dq, _ = jax.lax.scan(body, q, length=_TUNE_CHAIN)
        return dq

    return chain(jnp.zeros((bh, sq, d), dtype))


_FLASH_RUNNERS = {"flash_fwd": _run_fwd_candidate,
                  "flash_bwd": _run_bwd_candidate}


def _tuned_blocks(kernel, sq, sk, d, bh, dtype, is_causal, scale):
    """Consult the autotune cache (ops/autotune.py) for block geometry.

    Default policy is the heuristic table in _block_candidates (seeded by
    the round-2 END-TO-END on-chip sweep): isolated kernel timing
    mispicks here — it measured 128/128 fastest in isolation while the
    full train step is 43% slower with it than with 512/512, because the
    surrounding XLA schedule (fusions and DMA overlap across the custom
    call boundary) dominates the isolated delta. Set PTPU_AUTOTUNE_SWEEP=1
    to measure anyway (useful on new chip generations to re-seed the
    table; phi autotune/auto_tune_base.h analog)."""
    import os

    from . import autotune as at

    # ptpu-check[host-sync]: autotune keys on static shape/dtype/flag
    # config — these are trace-time constants, not traced values
    key = (bh, sq, sk, d, str(dtype), bool(is_causal))
    cands = _block_candidates(sq, sk)
    runner = None
    if os.environ.get("PTPU_AUTOTUNE_SWEEP") == "1":
        def runner(cfg):
            bq, bk = cfg

            def go():
                return _FLASH_RUNNERS[kernel](bh, sq, sk, d, dtype,
                                              is_causal, scale, bq, bk)
            return go

    return at.autotune("pallas_" + kernel, key, cands, runner)


def _interpret() -> bool:
    # PTPU_PALLAS_INTERPRET=1 runs the kernels in pallas interpret mode so
    # the CPU test mesh can exercise them (parity tests without a chip).
    # Honoured on the cpu platform only: on a chip it would silently swap
    # the compiled kernels for the interpreter.
    if _os.environ.get("PTPU_PALLAS_INTERPRET") != "1":
        return False
    platform = jax.devices()[0].platform
    if platform != "cpu":
        raise RuntimeError(
            f"PTPU_PALLAS_INTERPRET=1 is a CPU rehearsal switch; refusing "
            f"to interpret Pallas kernels on platform {platform!r}")
    return True


_SCOPED_VMEM_BYTES = 16 * 2 ** 20     # a kernel's VMEM unless it asks


def _flash_fwd(q, k, v, is_causal, scale, block_q=None, block_k=None,
               n_heads=1, mask=None, kv_lens=None, segments=None,
               kv_heads=None, window=None):
    """q,k,v: [BH, S, D] (heads folded into batch) → (out, lse).

    kv_heads: k, v fold fewer heads than q ([B * kv_heads, S, D]); the
    program of query head h reads K/V head h // (n_heads / kv_heads), and
    the runtime skips the copy while consecutive programs read the same
    one.  window (with is_causal): see `flash_attention_arrays`.

    mask: optional additive [B, Hm, Sq, Sk] with Hm in {1, n_heads} —
    loaded blockwise via its own BlockSpec, so a per-batch mask (Hm=1) is
    never broadcast-materialized per head in HBM (the reference fuses the
    same way: fused_softmax_mask_op reads the unexpanded mask).
    kv_lens: optional [B, 1] int32 valid key lengths — the padded-batch
    fast path: keys at positions >= len are masked IN the kernel and their
    blocks never DMA'd, with no [Sq, Sk] mask in HBM at all."""
    from jax.experimental import pallas as pl

    bh, sq, d = q.shape
    sk = k.shape[1]
    if block_q is None or block_k is None:
        block_q, block_k = _tuned_blocks(
            "flash_fwd", sq, sk, d, bh, q.dtype, is_causal, scale)
    # blocks must tile the sequence exactly — remainder blocks would leave
    # output rows unwritten (gated by _pallas_ok, asserted here)
    block_q = _largest_dividing_block(sq, block_q)
    block_k = _largest_dividing_block(sk, block_k)
    assert block_q is not None and block_k is not None

    H = n_heads
    Hk = kv_heads or H
    ratio = H // Hk
    has_mask = mask is not None
    has_lens = kv_lens is not None
    has_segs = segments is not None
    kernel = functools.partial(
        _flash_fwd_kernel,
        block_k=block_k,
        seq_k=sk,
        scale=scale,
        causal=is_causal,
        block_q=block_q,
        has_mask=has_mask,
        has_lens=has_lens,
        has_segs=has_segs,
        causal_offset=sk - sq,
        window=window,
    )
    grid = (bh // H, H, sq // block_q)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, h, i: (b * H + h, i, 0)),
        pl.BlockSpec((1, sk, d), lambda b, h, i: (b * Hk + h // ratio, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda b, h, i: (b * Hk + h // ratio, 0, 0)),
    ]
    args = [q, k, v]
    if has_lens:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, h, i: (b, 0)))
        args.append(kv_lens)
    if has_mask:
        bm, hm = mask.shape[0], mask.shape[1]
        in_specs.append(pl.BlockSpec(
            (1, 1, block_q, sk),
            lambda b, h, i: (b if bm > 1 else 0, h if hm > 1 else 0, i, 0)))
        args.append(mask)
    if has_segs:
        # segments: [B, S] int32 shared by q and k (packed self-attention)
        in_specs.append(pl.BlockSpec((1, block_q),
                                     lambda b, h, i: (b, i)))       # q block
        in_specs.append(pl.BlockSpec((1, sk),
                                     lambda b, h, i: (b, 0)))       # k row
        args.extend([segments, segments])
    # a head's whole K and V stand in VMEM, each buffered twice: past
    # the compiler's 16 MB of scoped VMEM (16,384 keys of 128 bf16 lanes
    # are 16.7 MB) the call asks for what it holds; shorter calls compile
    # as they always did
    held = 4 * sk * d * q.dtype.itemsize
    extra = {}
    if held > 3 * _SCOPED_VMEM_BYTES // 4:
        from jax.experimental.pallas import tpu as pltpu

        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=held + _SCOPED_VMEM_BYTES)
    return pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, h, i: (b * H + h, i, 0)),
            pl.BlockSpec((1, 1, sq), lambda b, h, i: (b * H + h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        interpret=_interpret(),
        **extra,
    )(*args)


def _flash_bwd(q, k, v, out, lse, do, is_causal, scale,
               block_q=None, block_k=None, n_heads=1, mask=None,
               kv_lens=None, segments=None):
    """Blockwise flash backward: recomputes p per tile from (q,k,lse) —
    no S^2 materialization in HBM. Returns (dq, dk, dv), all [BH, S, D]."""
    from jax.experimental import pallas as pl

    bh, sq, d = q.shape
    sk = k.shape[1]
    if block_q is None or block_k is None:
        block_q, block_k = _tuned_blocks(
            "flash_bwd", sq, sk, d, bh, q.dtype, is_causal, scale)
    block_q = _largest_dividing_block(sq, block_q)
    block_k = _largest_dividing_block(sk, block_k)
    assert block_q is not None and block_k is not None

    H = n_heads
    has_mask = mask is not None
    has_lens = kv_lens is not None
    has_segs = segments is not None
    bm = mask.shape[0] if has_mask else 1
    hm = mask.shape[1] if has_mask else 1
    interp = _interpret()

    do32 = do.astype(jnp.float32)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)[:, None, :]  # [bh,1,sq]

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, h, i: (b * H + h, i, 0)),
        pl.BlockSpec((1, sk, d), lambda b, h, i: (b * H + h, 0, 0)),
        pl.BlockSpec((1, sk, d), lambda b, h, i: (b * H + h, 0, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, h, i: (b * H + h, i, 0)),
        pl.BlockSpec((1, 1, sq), lambda b, h, i: (b * H + h, 0, 0)),
        pl.BlockSpec((1, 1, sq), lambda b, h, i: (b * H + h, 0, 0)),
    ]
    args = [q, k, v, do, lse, delta]
    if has_lens:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, h, i: (b, 0)))
        args.append(kv_lens)
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, 1, block_q, sk),
            lambda b, h, i: (b if bm > 1 else 0, h if hm > 1 else 0, i, 0)))
        args.append(mask)
    if has_segs:
        in_specs.append(pl.BlockSpec((1, block_q), lambda b, h, i: (b, i)))
        in_specs.append(pl.BlockSpec((1, sk), lambda b, h, i: (b, 0)))
        args.extend([segments, segments])
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, block_k=block_k, seq_k=sk,
                          scale=scale, causal=is_causal, block_q=block_q,
                          has_mask=has_mask, has_lens=has_lens,
                          has_segs=has_segs,
                          causal_offset=sk - sq),
        name="flash_bwd_dq",
        grid=(bh // H, H, sq // block_q),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d),
                               lambda b, h, i: (b * H + h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=interp,
    )(*args)

    in_specs = [
        pl.BlockSpec((1, sq, d), lambda b, h, i: (b * H + h, 0, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, h, i: (b * H + h, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, h, i: (b * H + h, i, 0)),
        pl.BlockSpec((1, sq, d), lambda b, h, i: (b * H + h, 0, 0)),
        pl.BlockSpec((1, 1, sq), lambda b, h, i: (b * H + h, 0, 0)),
        pl.BlockSpec((1, 1, sq), lambda b, h, i: (b * H + h, 0, 0)),
    ]
    args = [q, k, v, do, lse, delta]
    if has_lens:
        in_specs.append(pl.BlockSpec((1, 1), lambda b, h, i: (b, 0)))
        args.append(kv_lens)
    if has_mask:
        in_specs.append(pl.BlockSpec(
            (1, 1, sq, block_k),
            lambda b, h, i: (b if bm > 1 else 0, h if hm > 1 else 0, 0, i)))
        args.append(mask)
    if has_segs:
        in_specs.append(pl.BlockSpec((1, sq), lambda b, h, i: (b, 0)))
        in_specs.append(pl.BlockSpec((1, block_k), lambda b, h, i: (b, i)))
        args.extend([segments, segments])
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, block_q=block_q, seq_q=sq,
                          scale=scale, causal=is_causal, block_k=block_k,
                          has_mask=has_mask, has_lens=has_lens,
                          has_segs=has_segs,
                          causal_offset=sk - sq),
        name="flash_bwd_dkv",
        grid=(bh // H, H, sk // block_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, h, i: (b * H + h, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, h, i: (b * H + h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        interpret=interp,
    )(*args)
    return dq, dk, dv


def _mask_shape_ok(mask, B, H, sq, sk) -> bool:
    shp = mask.shape
    if len(shp) == 2:
        shp = (1, 1) + shp
    elif len(shp) == 3:
        shp = (shp[0], 1) + shp[1:]
    if len(shp) != 4:
        return False
    bm, hm, mq, mk = shp
    return (mq, mk) == (sq, sk) and bm in (1, B) and hm in (1, H)


def _pallas_ok(q, k, is_causal, mask, kv_lens=None, segment_ids=None,
               window=None) -> bool:
    if not (_on_tpu() or _interpret()):
        _count_path("attn_fallback:off_tpu")
        return False
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if h % k.shape[2]:
        _count_path("attn_fallback:kv_head_groups")
        return False
    if window is not None and (not is_causal or window < 1):
        _count_path("attn_fallback:window_not_causal")
        return False
    if d % 128 != 0 and d not in (64, 128, 256):
        _count_path("attn_fallback:head_dim")
        return False
    if _largest_dividing_block(sq) is None or _largest_dividing_block(sk) is None:
        _count_path("attn_fallback:seq_not_128_multiple")
        return False
    if mask is not None and not _mask_shape_ok(mask, b, h, sq, sk):
        _count_path("attn_fallback:mask_shape")
        return False
    if kv_lens is not None and tuple(kv_lens.shape) != (b,):
        _count_path("attn_fallback:kv_lens_shape")
        return False
    # (segment_ids shape is validated with a raise at the public entry —
    # flash_attention_arrays — since no dense fallback can serve a bad
    # shape either; no check here)
    if is_causal and sk - sq < 0:
        # causal with more queries than keys has no standard alignment
        _count_path("attn_fallback:causal_sq_gt_sk")
        return False
    return True


def _fold_heads(x):
    b, s, h, d = x.shape
    return jnp.moveaxis(x, 2, 1).reshape(b * h, s, d)


def _unfold_heads(x, b, h):
    bh, s, d = x.shape
    return jnp.moveaxis(x.reshape(b, h, s, d), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _flash_attn_core(q, k, v, mask, kv_lens, segs, is_causal, scale,
                     use_pallas):
    if use_pallas:
        b, s, h, d = q.shape
        of, _ = _flash_fwd(_fold_heads(q), _fold_heads(k), _fold_heads(v),
                           is_causal, scale, n_heads=h, mask=mask,
                           kv_lens=kv_lens, segments=segs)
        return _unfold_heads(of, b, h)
    return mha_reference(q, k, v, mask, is_causal, scale,
                         kv_lens=None if kv_lens is None else kv_lens[:, 0],
                         segment_ids=segs)


def _flash_attn_fwd(q, k, v, mask, kv_lens, segs, is_causal, scale,
                    use_pallas):
    if use_pallas:
        b, s, h, d = q.shape
        qf, kf, vf = _fold_heads(q), _fold_heads(k), _fold_heads(v)
        of, lse = _flash_fwd(qf, kf, vf, is_causal, scale, n_heads=h,
                             mask=mask, kv_lens=kv_lens, segments=segs)
        return _unfold_heads(of, b, h), (qf, kf, vf, of, lse, mask,
                                         kv_lens, segs, (b, h))
    out = mha_reference(q, k, v, mask, is_causal, scale,
                        kv_lens=None if kv_lens is None else kv_lens[:, 0],
                        segment_ids=segs)
    return out, (q, k, v, None, None, mask, kv_lens, segs, None)


def _flash_attn_bwd(is_causal, scale, use_pallas, res, g):
    q, k, v, out, lse, mask, kv_lens, segs, bh_shape = res
    # mask is additive: its cotangent exists but no caller consumes it
    dmask = None if mask is None else jnp.zeros_like(mask)
    dlens = (None if kv_lens is None
             else np.zeros(kv_lens.shape, jax.dtypes.float0))
    dsegs = (None if segs is None
             else np.zeros(segs.shape, jax.dtypes.float0))
    if use_pallas:
        b, h = bh_shape
        dq, dk, dv = _flash_bwd(q, k, v, out, lse, _fold_heads(g),
                                is_causal, scale, n_heads=h, mask=mask,
                                kv_lens=kv_lens, segments=segs)
        return (_unfold_heads(dq, b, h), _unfold_heads(dk, b, h),
                _unfold_heads(dv, b, h), dmask, dlens, dsegs)
    # XLA fallback: recompute-based backward through the reference
    _, vjp_fn = jax.vjp(
        lambda a, b, c: mha_reference(
            a, b, c, mask, is_causal, scale,
            kv_lens=None if kv_lens is None else kv_lens[:, 0],
            segment_ids=segs),
        q, k, v)
    return vjp_fn(g) + (dmask, dlens, dsegs)


_flash_attn_core.defvjp(_flash_attn_fwd, _flash_attn_bwd)


def _flash_kernel_on_mesh(q, k, v, mask, lens, segs, is_causal, scale):
    """The flash kernel call, per shard when a multi-device mesh is live.

    GSPMD cannot partition a Mosaic custom call ("Mosaic kernels cannot be
    automatically partitioned"), so once `init_mesh`/`set_mesh` has
    installed a mesh of more than one device the kernel runs under
    `jax.shard_map` over every mesh axis not already manual: [B,S,H,D]
    splits batch over ('dp','sharding') and heads over 'mp'; everything
    else is replicated into the call (GSPMD gathers an 'sp'-sharded
    sequence first).  Inside an enclosing shard_map (a 'pp' pipeline
    stage) the context mesh is used and its manual axes skipped.  Shapes
    that do not divide raise — there is no unsharded fallback."""
    from ..parallel.mesh import _current

    def kernel(*arrays):
        return _flash_attn_core(*arrays, is_causal, scale, True)

    ctx = jax.sharding.get_abstract_mesh()
    mesh = ctx if not ctx.empty else _current()
    free = [] if mesh is None else [
        a for a in mesh.axis_names if a not in ctx.manual_axes]
    if mesh is None or mesh.size == 1 or not free:
        return kernel(q, k, v, mask, lens, segs)
    batch = tuple(a for a in ("dp", "sharding")
                  if a in free and mesh.shape[a] > 1) or None
    heads = "mp" if "mp" in free and mesh.shape["mp"] > 1 else None
    b, h = q.shape[0], q.shape[2]
    n_b = math.prod(mesh.shape[a] for a in batch or ())
    n_h = mesh.shape[heads] if heads else 1
    if b % n_b or h % n_h:
        raise ValueError(
            f"flash attention on mesh {dict(mesh.shape)}: batch {b} must "
            f"divide over {batch} ({n_b}) and heads {h} over 'mp' ({n_h})")
    P = jax.sharding.PartitionSpec
    qkv = P(batch, None, heads, None)
    # an absent operand is an empty pytree: its spec is None too
    specs = (qkv, qkv, qkv,
             mask if mask is None else P(
                 batch if mask.shape[0] > 1 else None,
                 heads if mask.shape[1] > 1 else None, None, None),
             lens if lens is None else P(batch, None),
             segs if segs is None else P(batch, None))
    return jax.shard_map(
        kernel, mesh=None if not ctx.empty else mesh, in_specs=specs,
        out_specs=qkv, axis_names=frozenset(free), check_vma=False,
    )(q, k, v, mask, lens, segs)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_forward_only(q, k, v, is_causal, scale, window):
    """The flash forward kernel with grouped heads and/or a window.  It
    has no backward: differentiating it raises instead of tracing a JVP
    through the kernel."""
    b, _, h, _ = q.shape
    of, _ = _flash_fwd(_fold_heads(q), _fold_heads(k), _fold_heads(v),
                       is_causal, scale, n_heads=h, kv_heads=k.shape[2],
                       window=window)
    return _unfold_heads(of, b, h)


def _flash_forward_only_fwd(q, k, v, is_causal, scale, window):
    return _flash_forward_only(q, k, v, is_causal, scale, window), None


def _flash_forward_only_bwd(is_causal, scale, window, res, g):
    raise NotImplementedError(
        "flash attention with grouped heads or a window is served forward "
        "only; train through mha_reference")


_flash_forward_only.defvjp(_flash_forward_only_fwd, _flash_forward_only_bwd)


def _normalize_mask(attn_mask):
    """Bring a (shape-validated) user mask to additive [Bm, Hm, Sq, Sk]
    without broadcasting it out in HBM."""
    m = attn_mask
    if m.ndim == 2:
        m = m[None, None]
    elif m.ndim == 3:
        m = m[:, None]
    if m.dtype == jnp.bool_:
        m = jnp.where(m, jnp.float32(0), jnp.float32(_NEG_INF_MASK))
    return m


_NEG_INF_MASK = -1e30


def flash_attention_arrays(q, k, v, attn_mask=None, is_causal=False,
                           scale=None, kv_lens=None, segment_ids=None,
                           window=None):
    """Array-level entry (used inside compiled training steps).

    attn_mask on the KERNEL path is treated as a CONSTANT (stop_gradient):
    a flash kernel never materializes the [Sq, Sk] probability tile in HBM,
    so a mask cotangent would cost the O(S^2) write the kernel exists to
    avoid — the same contract as the reference's fused attention
    (fused_gate_attention does not emit a mask grad). Learned additive
    biases that need gradients should use `mha_reference` (or shapes that
    fall back to it), where the full vjp applies.

    kv_lens: optional [B] int32 per-sequence valid KEY length (>= 1) for
    right-padded variable-length batches — keeps the kernel path with NO
    [B,H,S,S] mask in HBM (the padded key blocks are never even DMA'd).
    Composable with is_causal and attn_mask.

    segment_ids: optional [B, S] int32 packed-sequence ids (the standard
    TPU pretraining input: multiple documents per row) — self-attention
    only; positions attend iff ids match, composed with is_causal. The
    kernel masks in-tile and SKIPS key blocks outside each q block's
    segment envelope, so packed batches keep flash cost with no [S, S]
    mask in HBM.

    window: optional int, with is_causal: a key is visible iff it lies
    fewer than `window` positions behind the query (sliding-window
    attention).  The kernel skips key blocks wholly behind a q block's
    window, so a long prompt pays S x window and not S^2.

    Grouped heads: k, v may hold fewer heads than q ([B, S, H_kv, D], H a
    multiple of H_kv); query head h reads K/V head h // (H / H_kv) and K/V
    are never repeated in HBM.  Grouped heads and `window` are served
    forward only (inference): this entry's backward does not carry them. Rows with an id that appears nowhere else (e.g. padding)
    produce unspecified output at those positions — ignore them, as with
    any padded attention. (SURVEY declares this capability class native —
    the reference has no flash kernels at all; analog masking semantics:
    praxis/flax segment_ids.)
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    lens = None
    if kv_lens is not None:
        lens = jax.lax.stop_gradient(
            jnp.asarray(kv_lens, jnp.int32).reshape(-1, 1))
    segs = None
    if segment_ids is not None:
        segs = jax.lax.stop_gradient(jnp.asarray(segment_ids, jnp.int32))
        b, sq, sk = q.shape[0], q.shape[1], k.shape[1]
        if sq != sk or tuple(segs.shape) != (b, sq):
            # no dense fallback exists either (segment attention is
            # self-attention with one [B, S] id array) — user error
            raise ValueError(
                f"segment_ids must be [batch, seq] = [{b}, {sq}] for "
                f"self-attention (got shape {tuple(segs.shape)}, "
                f"key length {sk})")
    grouped = k.shape[2] != q.shape[2]
    if grouped or window is not None:
        if attn_mask is not None or lens is not None or segs is not None:
            raise ValueError("grouped heads and window compose with "
                             "is_causal only")
        if _pallas_ok(q, k, is_causal, None, window=window):
            _count_path("attn_kernel" + (":grouped" if grouped else "")
                        + (":window" if window is not None else ""))
            return _flash_forward_only(q, k, v, is_causal, scale, window)
        return mha_reference(q, k, v, None, is_causal, scale, window=window)
    if _pallas_ok(q, k, is_causal, attn_mask,
                  None if lens is None else lens[:, 0], segs):
        _count_path("attn_kernel" + (":kv_lens" if lens is not None else "")
                    + (":segs" if segs is not None else "")
                    + (":causal_cross" if is_causal
                       and q.shape[1] != k.shape[1] else ""))
        mask = None
        if attn_mask is not None:
            mask = jax.lax.stop_gradient(_normalize_mask(attn_mask))
        return _flash_kernel_on_mesh(q, k, v, mask, lens, segs, is_causal,
                                     scale)
    return mha_reference(q, k, v, attn_mask, is_causal, scale,
                         kv_lens=None if lens is None else lens[:, 0],
                         segment_ids=segs)


def cached_attention_arrays(q, k, v, k_cache, v_cache, t, scale=None,
                            mask=None):
    """KV-cache attention for autoregressive decoding (reference CacheKV
    semantics: fused_multi_transformer_op.cu:90 — the fused op's cache_kv
    holds past keys/values and the new token is written at `time_step`).

    q, k, v:            [B, S, H, D] — the current chunk (S = prompt length
                        at prefill, 1 per decode step)
    k_cache, v_cache:   flat [B, S_max, H*D] rings (preferred — see the
                        layout note in the body) or legacy [B, S_max, H, D];
                        static shapes mean ONE XLA executable serves every
                        decode position (dynamic start index via
                        lax.dynamic_update_slice)
    t:                  int32 scalar — write position of the chunk's first
                        token (0 at prefill, current length during decode)
    mask:               optional extra mask over cache positions,
                        broadcastable to [B, H, S, S_max] — bool (True =
                        attend) or additive float; combined with the causal
                        mask (use for padded-prompt batches)

    Returns (out [B,S,H,D], new_k_cache, new_v_cache). Attention is causal
    over cache positions <= each query's absolute position; the O(S_max)
    masked-softmax XLA path is bandwidth-bound (MXU irrelevant at S_q=1),
    so no Pallas kernel is needed for correctness-first decode.
    """
    b, s, h, d = q.shape
    s_max = k_cache.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    t = jnp.asarray(t, jnp.int32)
    # caches may be [B, Smax, H, D] or flattened [B, Smax, H*D]. The flat
    # form is what decode wants: the (H, D) split never reaches any
    # buffer, so XLA has no reason to pick an (H, D)-tiled cache layout
    # that would force per-step relayout copies around the Pallas kernel
    # (whose view is flat anyway), and the one-row DUS write stays
    # contiguous.
    flat = k_cache.ndim == 3
    if flat:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.reshape(b, s, h * d).astype(k_cache.dtype), (0, t, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.reshape(b, s, h * d).astype(v_cache.dtype), (0, t, 0))
    else:
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, k.astype(k_cache.dtype), (0, t, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, v.astype(v_cache.dtype), (0, t, 0, 0))
    if mask is None and _decode_ok(q, k_cache, v_cache):
        # S_q=1 decode: Pallas kernel reads only the valid cache prefix
        out = flash_decode_arrays(q, k_cache, v_cache, t + 1, scale=scale)
        return out.astype(q.dtype), k_cache, v_cache
    kc4 = k_cache.reshape(b, s_max, h, d) if flat else k_cache
    vc4 = v_cache.reshape(b, s_max, h, d) if flat else v_cache
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kc4,
                        preferred_element_type=jnp.float32) * scale
    q_pos = t + jnp.arange(s, dtype=jnp.int32)          # absolute positions
    k_pos = jnp.arange(s_max, dtype=jnp.int32)
    causal = k_pos[None, :] <= q_pos[:, None]           # [S, S_max] causal
    logits = jnp.where(causal[None, None], logits, _NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, _NEG_INF)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vc4.dtype), vc4)
    return out.astype(q.dtype), k_cache, v_cache


def flash_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False,
    training=True, name=None, segment_ids=None
):
    """Tensor-level fused attention (nn.functional.scaled_dot_product_attention).
    segment_ids: optional [B, S] int ids for packed-sequence batches (see
    flash_attention_arrays)."""
    mask_arr = None
    if attn_mask is not None:
        mask_arr = attn_mask._data if isinstance(attn_mask, Tensor) else jnp.asarray(attn_mask)
    seg_arr = None
    if segment_ids is not None:
        seg_arr = (segment_ids._data if isinstance(segment_ids, Tensor)
                   else jnp.asarray(segment_ids))

    drop_key = _rng.next_key() if (dropout_p > 0.0 and training) else None

    def fn(q, k, v):
        out = flash_attention_arrays(q, k, v, mask_arr, is_causal,
                                     segment_ids=seg_arr)
        if drop_key is not None:
            keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, out.shape)
            out = jnp.where(keep, out / (1.0 - dropout_p), 0.0).astype(out.dtype)
        return out

    return apply(fn, query, key, value, name="flash_attention")


# ---------------------------------------------------------------------------
# Flash-decode kernel: single-token attention against a KV cache
# ---------------------------------------------------------------------------

def _decode_seg_helpers(h, d, fast):
    """Head-segmented matmul machinery shared by the decode kernels:
    Mosaic's (8,128) tiling forbids slicing H or D when they aren't tile
    multiples, so per-head logits come from one MXU matmul against the
    segment indicator (s = (K ∘ q) @ seg, [rows, H*D] @ [H*D, H]) and
    per-head weights expand back to lanes with its swapped twin. Both are
    built straight from 2D iotas (Mosaic cannot legalize transposes of
    these skinny shapes).  Where d IS a lane tile a head can be sliced
    out, and per-head MXU products are 13x faster on a v5e at d = 128
    (`ragged_paged_attention._head_stream`; PERF.md, PR 29: this body
    pays its indicator products per block whatever the block holds).
    The flash-decode kernels below (`generate()`, no serving cell) still
    take this body at every d."""
    hd = h * d
    seg = (jax.lax.broadcasted_iota(jnp.int32, (hd, h), 0) // d
           == jax.lax.broadcasted_iota(jnp.int32, (hd, h), 1)
           ).astype(fast)                                       # [hd, h]
    expand = (jax.lax.broadcasted_iota(jnp.int32, (h, hd), 0)
              == jax.lax.broadcasted_iota(jnp.int32, (h, hd), 1) // d
              ).astype(fast)                                    # [h, hd]

    def seg_dot(a3, mat, exact=False):
        """[bb, bk, X] @ [X, Y] -> [bb, bk, Y] via a free row-merge
        reshape. Default: operands in the cache's compute dtype (bf16
        caches → MXU fast path with fp32 accum, flash-standard for the
        big K/p products). exact=True keeps fp32 operands (HIGHEST) —
        required for the alpha/l rescale expansions, where low-precision
        rounding would compound across blocks."""
        rows = a3.shape[0] * a3.shape[1]
        a2 = a3.reshape(rows, a3.shape[2])
        if exact:
            out = _dot_f32(a2, mat.astype(jnp.float32))
        else:
            out = _dot_f32(a2.astype(fast), mat)
        return out.reshape(a3.shape[0], a3.shape[1], mat.shape[1])

    return seg, expand, seg_dot


def _two_block_dma_loop(num_kb, copies, step, carry, first_kb=0):
    """carry = step(slot, kb, carry) for kb in [first_kb, num_kb), two blocks per
    loop iteration: an iteration starts the DMAs of both its blocks
    (`copies(slot, kb)` builds block kb's descriptors into buffer `slot`),
    then `step` waits on each in turn and does its math — the second block
    streams in while the first computes, and `slot` is a PYTHON 0 or 1.
    A "block" is whatever `copies` fetches for one index: the ragged
    kernel's `_head_stream` makes it a tile of several pool blocks, each
    with a DMA of its own, all started and waited on in the one iteration.

    No DMA is ever in flight across a loop iteration.  The classic form —
    prefetch block kb+1 at the top of iteration kb, wait for it in the
    next — deadlocks on a v5e whenever the iteration also does the
    attention math (first hardware run, PR 21: DMA-only and math-only
    loops run, so does start+wait inside one iteration; traced and static
    slots deadlock alike; cause unknown).  Interpret mode, the TPU
    interpret mode with race detection included, cannot see it."""
    from jax.experimental import pallas as pl

    def start(slot, kb):
        for c in copies(slot, kb):
            c.start()

    def body(g, carry):
        kb0 = first_kb + 2 * g      # in range by the loop bound
        kb1 = kb0 + 1               # may fall off the end of the row
        start(0, kb0)

        @pl.when(kb1 < num_kb)
        def _start_second():
            start(1, kb1)

        carry = step(0, kb0, carry)
        return jax.lax.cond(kb1 < num_kb,
                            lambda c: step(1, kb1, c), lambda c: c, carry)

    return jax.lax.fori_loop(0, (num_kb - first_kb + 1) // 2, body, carry)


def _prefix_attn_loop(qf, length, num_kb, row0, k_hbm, v_hbm, k_buf, v_buf,
                      sem, seg, expand, seg_dot, *, bb, block_k, h, scale):
    """Online-softmax attention of qf [bb, 1, H*D] (fp32) against cache
    rows [row0:row0+bb, 0:length) streamed from HBM two blocks at a time.
    Returns the running (m, l, acc) softmax state ([bb,1,H] / [bb,1,H*D]
    fp32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hd = qf.shape[-1]

    def copies(slot, kb):
        start = kb * block_k
        src_k = k_hbm.at[pl.ds(row0, bb), pl.ds(start, block_k)]
        src_v = v_hbm.at[pl.ds(row0, bb), pl.ds(start, block_k)]
        return (pltpu.make_async_copy(src_k, k_buf.at[slot], sem.at[slot, 0]),
                pltpu.make_async_copy(src_v, v_buf.at[slot], sem.at[slot, 1]))

    def step(slot, kb, carry):
        m, l, acc = carry          # m,l: [bb,1,H]; acc: [bb,1,H*D] fp32
        start = kb * block_k
        kd, vd = copies(slot, kb)
        kd.wait()
        kf = k_buf[slot].astype(jnp.float32)                     # [bb,bk,hd]
        s = seg_dot(kf * qf, seg) * scale                        # [bb,bk,H]
        pos = start + jax.lax.broadcasted_iota(
            jnp.int32, (bb, block_k, h), 1)
        s = jnp.where(pos < length, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                                   # [bb,bk,H]
        alpha = jnp.exp(m - m_new)                               # [bb,1,H]
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        vd.wait()
        vf = v_buf[slot].astype(jnp.float32)                     # [bb,bk,hd]
        pexp = seg_dot(p, expand)                                # [bb,bk,hd]
        pv = jnp.sum(pexp * vf, axis=1, keepdims=True)           # [bb,1,hd]
        acc_new = acc * seg_dot(alpha, expand, exact=True) + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((bb, 1, h), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bb, 1, h), jnp.float32)
    acc0 = jnp.zeros((bb, 1, hd), jnp.float32)
    return _two_block_dma_loop(num_kb, copies, step, (m0, l0, acc0))


def _decode_kernel(len_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sem,
                   *, block_b, block_k, h, d, scale):
    """One program per batch slab: q [bb, 1, H*D] against the valid prefix
    of the caches [B, S_max, H*D] living in HBM. The valid length arrives
    via scalar prefetch (len_ref), so only ceil(len / block_k) cache
    blocks are ever DMA'd into VMEM — the XLA fallback reads (and masks)
    all S_max positions — two at a time (`_two_block_dma_loop`), so the
    second block streams in while the first computes. Heads live
    flattened in the lane dim: Mosaic's (8,128) tiling forbids slicing H
    or D when they aren't tile multiples, so per-head logits come from one
    MXU matmul against the segment indicator (s = (K ∘ q) @ seg,
    [bb*bk, H*D] @ [H*D, H]) and the per-head softmax weights are expanded
    back to lanes with its swapped twin (p @ E, [bb*bk, H] @ [H, H*D]).
    Online softmax over blocks, fp32 accumulation."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    ib = pl.program_id(0)
    length = len_ref[0]
    num_kb = (length + block_k - 1) // block_k   # 0 blocks -> zeros out
    bb = block_b
    qf = q_ref[...].astype(jnp.float32)                          # [bb,1,hd]
    # _dot_f32 contract: bf16 caches ride the MXU's fast path (flash-
    # standard), fp32 caches keep fp32-HIGHEST correctness
    fast = jnp.bfloat16 if k_buf.dtype == jnp.bfloat16 else jnp.float32
    seg, expand, seg_dot = _decode_seg_helpers(h, d, fast)
    m, l, acc = _prefix_attn_loop(
        qf, length, num_kb, ib * bb, k_hbm, v_hbm, k_buf, v_buf, sem,
        seg, expand, seg_dot, bb=bb, block_k=block_k, h=h, scale=scale)
    l_exp = seg_dot(l, expand, exact=True)                       # [bb,1,hd]
    o_ref[...] = (acc / jnp.maximum(l_exp, 1e-30)).astype(o_ref.dtype)


def flash_decode_arrays(q, k_cache, v_cache, length, scale=None,
                        block_k=256):
    """Decode-attention against the first `length` cache positions.

    q [B, 1, H, D]; k_cache/v_cache [B, S_max, H, D]; length: int32 scalar
    (t + 1 during decode). Returns [B, 1, H, D]. The TPU answer to the
    reference's masked full-cache attention inside
    fused_multi_transformer_op.cu's decode branch: at S_q = 1 the MXU is
    idle and HBM bandwidth on cache reads is everything, so the kernel
    reads only the valid cache prefix (blockwise DMA, online softmax)
    instead of all S_max rows."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, s, h, d = q.shape
    s_max = k_cache.shape[1]
    assert s == 1, "flash_decode_arrays is the S_q=1 path"
    if k_cache.ndim == 4:               # [B, Smax, H, D] → flat lane view
        k_cache = k_cache.reshape(b, s_max, h * d)
        v_cache = v_cache.reshape(b, s_max, h * d)
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    # blocks must tile s_max exactly: the DMA loop reads whole blocks, and a
    # ragged final block would read past the cache rows
    block_k = min(block_k, s_max)
    while s_max % block_k:
        block_k //= 2
    # prefer >= 2 seq blocks so the second block's DMA overlaps the first
    if s_max // block_k < 2 and block_k >= 16 and s_max % (block_k // 2) == 0:
        block_k //= 2
    # batch slab: largest divisor of B whose two-block k+v slabs
    # ([2, bb, block_k, H*D] each) stay within ~8 MiB of VMEM; keep
    # block_k a sublane multiple so the seq-slice DMA stays tile-aligned
    itemsize = jnp.dtype(k_cache.dtype).itemsize
    block_b = b
    while block_b > 1 and (b % block_b
                           or 4 * block_b * block_k * h * d * itemsize
                           > 8 * 2**20):
        block_b -= 1
    while (block_k > 8
           and 4 * block_b * block_k * h * d * itemsize > 8 * 2**20):
        block_k //= 2
    assert block_k % 8 == 0 or block_k == s_max

    # One program per batch slab. Heads are flattened into the lane dim
    # ([B, S, H*D] views — free reshapes of trailing contiguous dims): the
    # cache DMA then slices only untiled/aligned dims, and q/o blocks'
    # last two dims (1, H*D) equal the array dims — Mosaic requires
    # blocks' last two dims be (8,128)-divisible OR full, and forbids
    # slicing H or D when they aren't tile multiples (interpret mode
    # never checks this).
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, 1, h * d), lambda i, len_ref: (i, 0, 0)),
            # pin caches to HBM: under ANY, Mosaic may place them in VMEM
            # and the kernel's whole point is NOT streaming them there
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ],
        out_specs=pl.BlockSpec((block_b, 1, h * d),
                               lambda i, len_ref: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, block_b, block_k, h * d), k_cache.dtype),
            pltpu.VMEM((2, block_b, block_k, h * d), v_cache.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    kernel = functools.partial(_decode_kernel, block_b=block_b,
                               block_k=block_k, h=h, d=d, scale=scale)
    lengths = jnp.asarray(length, jnp.int32).reshape(1)
    out = pl.pallas_call(
        kernel,
        name="flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, 1, h * d), q.dtype),
        interpret=_interpret(),
    )(lengths, q.reshape(b, 1, h * d), k_cache, v_cache)
    return out.reshape(b, 1, h, d)


def _decode_ok(q, k_cache, v_cache) -> bool:
    import os
    forced = os.environ.get("PTPU_FLASH_DECODE")
    if forced == "0":
        _count_path("decode_fallback:disabled")
        return False
    if not (_on_tpu() or _interpret()):
        _count_path("decode_fallback:off_tpu")
        return False
    b, s, h, d = q.shape
    s_max = k_cache.shape[1]
    if s != 1:
        _count_path("decode_fallback:chunk_gt_1")
        return False
    if d not in (64, 128, 256) or (h * d) % 128 != 0:
        _count_path("decode_fallback:head_geometry")
        return False
    if s_max % 128 != 0:
        _count_path("decode_fallback:smax_not_128_multiple")
        return False
    # same-dtype: the kernel's lax.dot_general needs matching operands (the
    # XLA fallback einsum would promote mixed fp32-q/bf16-cache instead)
    if not (q.dtype == k_cache.dtype == v_cache.dtype):
        _count_path("decode_fallback:dtype_mix")
        return False
    if forced != "1":
        # auto policy (checked LAST so counter attribution stays honest):
        # at short caches the kernel's fixed costs (launch, DMA double-
        # buffer priming) dominate the tiny prefix read and the XLA
        # masked full-cache path wins (round-2 bisect: ~0.23 ms/layer at
        # S_max=256 vs a ~0.02 ms bound); prefix-skipping pays off once
        # the cache is long. PTPU_FLASH_DECODE=1/0 forces either way.
        try:
            min_smax = int(
                os.environ.get("PTPU_FLASH_DECODE_MIN_SMAX", "1024"))
        except ValueError:
            min_smax = 1024
        if s_max < min_smax:
            _count_path("decode_fallback:small_smax")
            return False
    _count_path("decode_kernel")
    return True
