"""Ragged paged attention — ONE fixed-shape fused program for mixed-length
prefill/decode rows over the block-paged KV pools ("Ragged Paged
Attention", PAPERS.md), with the current tokens' cache update pulled into
the same program (the MPK fuse-across-boundaries lever, PAPERS.md) and —
for the int8 KV wing — the per-block-per-head dequant applied at the K/V
block loads instead of as a separate gather-dequantize pass.

This is the serving decode workhorse ISSUE 8 / ROADMAP item 1 calls for:
the round-2 bisect pinned ~2.77 ms of the 3.34 ms decode step to the
gather-blocks → masked-attention → cache-scatter triple, and a batch
padded to the running-request count recompiles whenever that count
changes.  Here the engine compiles ONE program at ``[max_num_seqs, 1]``
and every batch composition runs it.

Two implementations behind one entry point, selected like
``pallas_ops._pallas_ok`` (PTPU_ATTN_DEBUG=1 counts every gate decision):

- **Pallas kernel** (TPU, or CPU under ``PTPU_PALLAS_INTERPRET=1``), the
  decode (S_q = 1) shape: one program per row streams ONLY the row's
  ``ceil(len / block_size)`` physical blocks from HBM with an online
  softmax (the XLA fallback touches all ``max_blocks`` gathered rows),
  fuses the new token's quantize+scatter as a read-modify-write of the
  row's last block BEFORE the stream (pools are aliased in place).  The
  stream has two bodies, chosen from head dim and pool dtype
  (`_head_products_ok`).  Full-precision pools whose heads are whole lane
  tiles (d = 128: both GPT benchmark configurations and afmoe): a loop
  step takes a TILE of 64 tokens - ``64 // block_size`` table entries
  gathered by as many DMAs into one VMEM buffer - and runs two MXU
  products per K/V head over it, the query heads that share the K/V head
  as the products' rows (`_head_stream`; two tiles in flight per loop
  iteration).  Heads of 64 lanes and the int8 pools: one block a step,
  heads flattened in the lanes and reduced through segment-indicator
  matmuls, int8 blocks dequantized at load time — the int8 codes never
  exist as a dequantized [B, S_pad, H, D] float tensor anywhere.

- **XLA array-level fallback** (any backend, any chunk width C): the
  cache update and attention of `ops.paged_attention` composed in one
  function.  The full-precision path is BITWISE the reference
  (`paged_cache_update_arrays` + `paged_attention_arrays`) — that is what
  keeps mixed continuous batches token-identical to solo dense
  ``generate()`` on the ragged engine path.  The int8 path reuses
  `quantized_cache_update_arrays` bitwise but replaces the dequantizing
  gather with a scale-FOLDED attention: it gathers int8 CODES (1 byte per
  element instead of the 4-byte fp32 dequant materialization) plus the
  tiny per-position scales, and applies ``k_scale`` to the logits and
  ``v_scale`` to the probabilities — algebraically identical because the
  scale is constant along the contracted head_dim axis, within a last-ulp
  reassociation of the dequantize-then-einsum reference (int8 KV parity
  is a documented tolerance, PR 4; all engine rows share one arithmetic
  so engine-vs-engine invariants stay bitwise).  It never calls
  `quantized_gather_kv_arrays`, so the ragged path makes no
  ``lowbit/dequant_calls{site="paged_gather"}`` increments.

A LATENT pool (ISSUE 34, `ragged_latent_attention_arrays`): one pool a
layer whose row is a token's latent, key and - in its leading lanes -
value of every query head alike.  The decode kernel (`_latent_kernel`)
DMAs a tile of rows ONCE and takes both MXU products from the same buffer,
the query heads as their rows: `q~ [H, lanes] x rows^T` and `p [H, T] x
rows[:, :value_dim]`.  The fallback is `latent_cache_update_arrays` +
`latent_paged_attention_arrays`.

Numerics contract of the fallback: same einsum contraction (fp32
accumulation), same additive -1e30 causal mask over the SAME padded
[B, max_blocks * block_size] extent, same softmax/probs-cast as
`paged_attention_arrays` — positions past a row's true length underflow
to an exact 0 probability.  The kernel's online softmax reorders the
reductions (last-ulp, like flash decode vs the dense reference); it is
gated off the CPU parity path and pinned against the fallback by
tests/test_ragged_attention.py.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp

from .paged_attention import (latent_cache_update_arrays,
                              latent_paged_attention_arrays,
                              paged_attention_arrays,
                              paged_cache_update_arrays,
                              quantized_cache_update_arrays)
from .pallas_ops import (_NEG_INF, _count_path, _decode_seg_helpers,
                         _dot_f32, _interpret, _on_tpu, _two_block_dma_loop)

__all__ = ["ragged_paged_attention_arrays",
           "ragged_latent_attention_arrays"]

_QMAX = 127


# ---------------------------------------------------------------------------
# dispatch gate (the _pallas_ok idiom: every decision counted under
# PTPU_ATTN_DEBUG=1 so serving shapes silently dropping to the fallback
# are observable)
# ---------------------------------------------------------------------------

def _decode_kernel_wanted(c) -> bool:
    """What both decode kernels' gates ask first: the flag, the platform,
    the chunk width (the kernels serve C = 1)."""
    if os.environ.get("PTPU_RAGGED_KERNEL", "").lower() in ("0", "false",
                                                            "off"):
        _count_path("ragged_fallback:disabled")
        return False
    if not (_on_tpu() or _interpret()):
        _count_path("ragged_fallback:off_tpu")
        return False
    if c != 1:
        _count_path("ragged_fallback:chunk_gt_1")
        return False
    return True


def _ragged_kernel_ok(q, k_blocks, c, quant, window=None) -> bool:
    """Geometry/flag gate for the fused ragged kernel.  The kernel serves
    the decode shape (C = 1) — chunked-prefill and speculative-verify
    rows (C > 1) take the fallback, which is the parity-exact program
    anyway (a multi-token kernel variant is the natural follow-up once
    the verify path earns its on-chip A/B).
    PTPU_RAGGED_KERNEL=0 hard-disables."""
    if not _decode_kernel_wanted(c):
        return False
    _, _, h, d = q.shape
    bs = int(k_blocks.shape[1])
    hd_kv = int(k_blocks.shape[2])
    if d not in (64, 128, 256) or hd_kv % 128 != 0:
        _count_path("ragged_fallback:head_geometry")
        return False
    if hd_kv % d or (h * d) % hd_kv or h * d // hd_kv > _GROUP_ROWS:
        # query heads must be a whole number of groups over the K/V heads
        _count_path("ragged_fallback:kv_head_groups")
        return False
    grouped = h * d != hd_kv
    if grouped and d % 128 and not (
            d == 64 and 2 * (h * d // hd_kv) <= _GROUP_ROWS):
        # a grouped K/V head is read as whole lane tiles of a block, or
        # two heads of 64 lanes as one tile whose 2 x G query heads fit
        # the products' rows
        _count_path("ragged_fallback:grouped_head_dim")
        return False
    if quant and (h * d != hd_kv or window is not None):
        # int8 pools are read with as many K/V heads as query heads and
        # no window (the scale tables are gathered per query head)
        _count_path("ragged_fallback:quant_grouped_or_window")
        return False
    if window is not None and window < 1:
        _count_path("ragged_fallback:window_lt_1")
        return False
    # block DMAs slice [block_size, H*D] slabs: the sublane dim must be a
    # tile multiple for the pool dtype ((8,128) f32 / (16,128) bf16 /
    # (32,128) int8)
    sub = 32 if quant else (16 if k_blocks.dtype == jnp.bfloat16 else 8)
    if bs % sub != 0:
        _count_path("ragged_fallback:block_size")
        return False
    if not quant and q.dtype != k_blocks.dtype:
        # the kernel's matmuls want matching operand dtypes (the XLA
        # fallback einsum promotes mixed q/pool dtypes instead)
        _count_path("ragged_fallback:dtype_mix")
        return False
    _count_path("ragged_kernel")
    _count_path("ragged_kernel:head_products"
                if _head_products_ok(d, quant, grouped)
                else "ragged_kernel:segment_products")
    return True


# tokens of a row's K/V one step of the stream consumes, as
# `_TILE_TOKENS // block_size` table entries gathered into one VMEM buffer:
# the block at which PR 28 measured the per-head products at 37% of the
# HBM roofline (0.85 us for 256 KB; at 16 tokens a step the same loop read
# 3.6%, each step paying its DMA round trip and its MXU weight loads for a
# quarter of the rows)
_TILE_TOKENS = 64


def _head_products_ok(d, quant, grouped=False) -> bool:
    """Which body the stream takes, from what the call can see.  A head
    that is whole lane tiles of a full-precision pool row is sliced out
    and multiplied on the MXU (`_head_stream`), whatever the number of
    query heads that read it, one included.  A head of 64 lanes cannot be
    sliced out; where query heads share it (`grouped`), two such heads
    are one lane tile and the stream multiplies the pair (`_pair_members`).
    Ungrouped heads of 64 lanes and the int8 pools, whose scales are
    gathered per head beside the codes, keep the segment-indicator
    body."""
    return (d % 128 == 0 or (grouped and d == 64)) and not quant


# ---------------------------------------------------------------------------
# the fused kernel (S_q = 1): cache update (read-modify-write of the
# row's last block) then a streamed attention over the row's blocks
# ---------------------------------------------------------------------------

def _ragged_fused_kernel(len_ref, slot_ref, tbl_ref, q_ref, kn_ref, vn_ref,
                         k_hbm, v_hbm, *refs, bs, h, d, nb, maxb, scale,
                         quant, window=None, heads=False):
    """One program per batch row r:

    1. DMA the row's TARGET block (the one its write slot lands in) into
       VMEM, splice/quantize the new token's K/V row in (int8: rescale
       the existing codes from the block's old scale to its grown one
       exactly like `quantized_cache_update_arrays`; the [num_blocks, H]
       scale tables themselves are grown by the caller in XLA, because a
       one-row (1, H) DMA into them is below Mosaic's tile), DMA it
       back — pools are aliased in place, and blocks a row writes are
       always privately owned (the engine privatizes shared last blocks
       at fork), so programs never race.
    2. Stream the row's K/V from HBM through its block table in SMEM with
       an online softmax.  The row's own new token never comes back
       through the alias: the stream takes it from `kn_ref`/`vn_ref`
       (`_head_stream`) or from the updated VMEM copy of the target
       block (the segment body below).

    `h` counts the K/V heads of a pool row.  `heads` (`_head_products_ok`)
    picks the body of step 2: `_head_stream`, or here the segment body for
    heads of 64 lanes and int8 pools - as many query heads as K/V heads,
    q `[1, 1, h*d]`, one block a step, two a loop iteration
    (`_two_block_dma_loop`).  There the heads stay flattened in the lane
    dim and per-head logits/weights go through the segment-indicator
    matmuls of `_decode_seg_helpers` (a head that is not whole lane tiles
    cannot be sliced out of a row under Mosaic's (8,128) tiling), int8
    codes dequantized at load via the per-block-per-head scales.

    `window`: the stream starts at block `max(0, length - window) // bs`
    and positions under `length - window` are masked (table entries
    behind that may point nowhere; they are never read).

    Rows whose write slot is out of range (batch padding / evicted rows)
    skip the write; a row of length 0 streams nothing and puts out
    zeros."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    refs = list(refs)
    if quant:
        gks_ref, gvs_ref, oks_ref, nks_ref, ovs_ref, nvs_ref = refs[:6]
        refs = refs[6:]
    o_ref, ko_hbm, vo_hbm, kbuf, vbuf, sem, ublk, usem = refs
    hd = h * d
    r = pl.program_id(0)
    length = jnp.maximum(len_ref[r], 0)
    slot = slot_ref[r]
    valid = (slot >= 0) & (slot < nb * bs)
    blk = jnp.clip(slot // bs, 0, nb - 1)
    off = jnp.where(valid, slot % bs, 0)

    # -- 1. fused cache update ---------------------------------------------
    rk = pltpu.make_async_copy(k_hbm.at[pl.ds(blk, 1)], ublk.at[0],
                               usem.at[0])
    rv = pltpu.make_async_copy(v_hbm.at[pl.ds(blk, 1)], ublk.at[1],
                               usem.at[1])
    rk.start()
    rv.start()
    rk.wait()
    rv.wait()
    off_mask = (jax.lax.broadcasted_iota(jnp.int32, (1, bs, 1), 1) == off)

    if quant:
        seg, expand, seg_dot = _decode_seg_helpers(h, d, jnp.float32)

        def _sel_row(g_ref, kb):
            # row kb of the pre-gathered [1, maxb, h] scale view as
            # [1, h] — masked sublane sum instead of a dynamic VMEM slice
            rows = g_ref[...][0]                         # [maxb, h]
            mask = (jax.lax.broadcasted_iota(jnp.int32, (maxb, 1), 0)
                    == kb)
            return jnp.sum(jnp.where(mask, rows, 0.0), axis=0,
                           keepdims=True)

        def _quant_update(xn_ref, old_ref, new_ref, blk_codes):
            # mirrors quantized_cache_update_arrays for ONE incoming row:
            # existing codes rescale by old/new scale (exactly 1.0 when
            # unchanged — bit-stable steady state); the row quantizes
            # against the new scale
            old_s, new_s = old_ref[...], new_ref[...]    # [1, 1, h]
            factor = jnp.where(
                new_s > 0, old_s / jnp.where(new_s > 0, new_s, 1.0), 1.0)
            fac_hd = seg_dot(factor, expand, exact=True)
            resc = jnp.clip(
                jnp.round(blk_codes.astype(jnp.float32) * fac_hd),
                -_QMAX, _QMAX)
            s_hd = seg_dot(new_s, expand, exact=True)
            safe = jnp.where(s_hd > 0, s_hd, 1.0)
            qrow = jnp.clip(
                jnp.round(xn_ref[...].astype(jnp.float32) / safe),
                -_QMAX, _QMAX)
            codes = jnp.where(off_mask & valid, qrow, resc)  # [1, bs, hd]
            return codes, s_hd

        k_codes, ks_hd = _quant_update(kn_ref, oks_ref, nks_ref, ublk[0])
        v_codes, vs_hd = _quant_update(vn_ref, ovs_ref, nvs_ref, ublk[1])
        ublk[0] = k_codes.astype(jnp.int8)
        ublk[1] = v_codes.astype(jnp.int8)
        kup_f = k_codes * ks_hd          # dequantized local target block
        vup_f = v_codes * vs_hd
    else:
        ublk[0] = jnp.where(off_mask & valid,
                            kn_ref[...].astype(ublk.dtype), ublk[0])
        ublk[1] = jnp.where(off_mask & valid,
                            vn_ref[...].astype(ublk.dtype), ublk[1])

    @pl.when(valid)
    def _writeback():
        wk = pltpu.make_async_copy(ublk.at[0], ko_hbm.at[pl.ds(blk, 1)],
                                   usem.at[0])
        wv = pltpu.make_async_copy(ublk.at[1], vo_hbm.at[pl.ds(blk, 1)],
                                   usem.at[1])
        wk.start()
        wv.start()
        # writes must complete before the stream below may read the same
        # HBM region (what it reads at the new token's position is
        # discarded, but an in-flight overlapping read/write would be
        # undefined)
        wk.wait()
        wv.wait()

    # -- 2. streamed attention over the row's valid blocks ------------------
    low = None if window is None else jnp.maximum(length - window, 0)

    def seen_at(pos, end):
        seen = pos < end
        return seen if low is None else seen & (pos >= low)

    if heads:
        _head_stream(r, tbl_ref, q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref,
                     kbuf, vbuf, sem, length, valid, low, seen_at, bs=bs,
                     h=h, d=d, nb=nb, maxb=maxb, scale=scale)
        return

    # the write slot is the row's LAST position (length - 1), so the
    # target block is the last logical block the attention stream visits
    tkb = jnp.where(valid, jnp.clip((length - 1) // bs, 0, maxb - 1), -1)
    num_kb = jnp.minimum((length + bs - 1) // bs, maxb)
    first_kb = 0 if low is None else jnp.minimum(low // bs, num_kb)
    if not quant:
        seg, expand, seg_dot = _decode_seg_helpers(
            h, d, jnp.bfloat16 if kbuf.dtype == jnp.bfloat16
            else jnp.float32)
        kup_f = ublk[0].astype(jnp.float32)
        vup_f = ublk[1].astype(jnp.float32)

    def copies(slot_i, kb):
        b_kb = jnp.clip(tbl_ref[r, kb], 0, nb - 1)
        return (pltpu.make_async_copy(k_hbm.at[pl.ds(b_kb, 1)],
                                      kbuf.at[slot_i], sem.at[slot_i, 0]),
                pltpu.make_async_copy(v_hbm.at[pl.ds(b_kb, 1)],
                                      vbuf.at[slot_i], sem.at[slot_i, 1]))

    qf = q_ref[...].astype(jnp.float32)                  # [1, 1, hd]

    def step(sl, kb, carry):
        m, l, acc = carry            # m, l: [1,1,h]; acc: [1,1,hd] fp32
        kd, vd = copies(sl, kb)
        kd.wait()
        is_t = valid & (kb == tkb)
        kf = kbuf[sl].astype(jnp.float32)                # [1, bs, hd]
        if quant:
            kf = kf * seg_dot(_sel_row(gks_ref, kb)[:, None, :], expand,
                              exact=True)
        kf = jnp.where(is_t, kup_f, kf)
        s = seg_dot(kf * qf, seg) * scale                # [1, bs, h]
        pos = kb * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs, h), 1)
        s = jnp.where(seen_at(pos, length), s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        vd.wait()
        vf = vbuf[sl].astype(jnp.float32)
        if quant:
            vf = vf * seg_dot(_sel_row(gvs_ref, kb)[:, None, :], expand,
                              exact=True)
        vf = jnp.where(is_t, vup_f, vf)
        pexp = seg_dot(p, expand)                        # [1, bs, hd]
        pv = jnp.sum(pexp * vf, axis=1, keepdims=True)
        acc_new = acc * seg_dot(alpha, expand, exact=True) + pv
        return m_new, l_new, acc_new

    m0 = jnp.full((1, 1, h), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((1, 1, h), jnp.float32)
    acc0 = jnp.zeros((1, 1, hd), jnp.float32)
    m, l, acc = _two_block_dma_loop(num_kb, copies, step, (m0, l0, acc0),
                                    first_kb=first_kb)
    l_exp = seg_dot(l, expand, exact=True)
    o_ref[...] = (acc / jnp.maximum(l_exp, 1e-30)).astype(o_ref.dtype)


_GROUP_ROWS = 8        # a row's group members, padded to one sublane tile


def _head_stream(r, tbl_ref, q_ref, kn_ref, vn_ref, k_hbm, v_hbm, o_ref,
                 kbuf, vbuf, sem, length, valid, low, seen_at, *, bs, h, d,
                 nb, maxb, scale):
    """Step 2 of `_ragged_fused_kernel` where a K/V head is whole lane
    tiles of a full-precision pool row.  q_ref/o_ref are `[1, GP, h*d]`:
    the query heads that read K/V head j are the rows of lane segment j
    (member g is query head j * G + g), padded with zero rows to GP = 8
    sublanes - or `[1, 1, h*d]` for a row with as many query heads as K/V
    heads, a group of one, whose row is repeated GP times here and put
    out once.  A step takes a TILE of `kbuf.shape[2] // bs` table entries,
    their blocks gathered by as many DMAs into one `[tile * bs, h*d]`
    buffer, and runs per K/V head two MXU products over it with the
    members as their rows - `q_j [GP, d] x K_j^T` and `p [GP, T] x V_j` -
    with the online-softmax state kept per head: m, l `[GP, 1]` and acc
    `[GP, d]` in float32.  Two tiles a loop iteration, every DMA started
    and waited on inside it (`_two_block_dma_loop`, its blocks here being
    tiles counted from the stream's first table entry).

    The row's new token is the state the stream starts from (its logit
    from `kn_ref`, its value from `vn_ref`), and position `length - 1` is
    masked in what is streamed.  Entries of a row's last tile past its
    last block fetch that last block again: whatever lands in a buffer is
    a block the row owns, and every position past the row's end is
    masked, so no product ever meets a buffer that was not filled."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    gp = _GROUP_ROWS
    t_rows = kbuf.shape[2]
    tile = t_rows // bs
    members = q_ref.shape[1]                              # GP, or 1
    lanes_of = [slice(j * d, (j + 1) * d) for j in range(h)]
    # sliced from the ref head by head (a lane slice of a loaded one-row
    # value has no Mosaic layout)
    q_of = [jnp.broadcast_to(q_ref[0, :, lanes], (gp, d))
            for lanes in lanes_of]
    has_new = valid & (length > 0)
    # positions the stream answers for: the new token's own is the state
    n_old = jnp.where(has_new, length - 1, length)
    num_kb = jnp.minimum((n_old + bs - 1) // bs, maxb)
    first_kb = 0 if low is None else jnp.minimum(low // bs, num_kb)

    def copies(slot_i, t):          # the tile's K descriptors, then its V
        k_dmas, v_dmas = [], []
        for i in range(tile):
            kb = jnp.minimum(first_kb + t * tile + i, num_kb - 1)
            b_kb = jnp.clip(tbl_ref[r, kb], 0, nb - 1)
            rows = pl.ds(i * bs, bs)
            k_dmas.append(pltpu.make_async_copy(
                k_hbm.at[pl.ds(b_kb, 1)], kbuf.at[slot_i, :, rows],
                sem.at[slot_i, 0]))
            v_dmas.append(pltpu.make_async_copy(
                v_hbm.at[pl.ds(b_kb, 1)], vbuf.at[slot_i, :, rows],
                sem.at[slot_i, 1]))
        return k_dmas + v_dmas

    def step(sl, t, carry):
        dmas = copies(sl, t)
        for c in dmas[:tile]:
            c.wait()
        k_t = kbuf[sl, 0]                                 # [T, hd]
        pos = ((first_kb + t * tile) * bs
               + jax.lax.broadcasted_iota(jnp.int32, (gp, t_rows), 1))
        seen = seen_at(pos, n_old)
        half = []
        for (m, l, _), q_j, lanes in zip(carry, q_of, lanes_of):
            s = _dot_f32(q_j, k_t[:, lanes],
                         transpose_b=True) * scale        # [GP, T]
            s = jnp.where(seen, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            half.append((m_new, alpha * l + jnp.sum(p, axis=1,
                                                    keepdims=True),
                         p, alpha))
        for c in dmas[tile:]:
            c.wait()
        v_t = vbuf[sl, 0]
        return tuple(
            (m_new, l_new,
             acc * alpha + _dot_f32(p.astype(v_t.dtype), v_t[:, lanes]))
            for (m_new, l_new, p, alpha), (_, _, acc), lanes
            in zip(half, carry, lanes_of))

    def new_row(ref, lanes):          # [1, d] float32, as the pool keeps it
        return ref[0, :, lanes].astype(kbuf.dtype).astype(jnp.float32)

    state0 = tuple(
        (jnp.where(has_new, scale * jnp.sum(
            q_j.astype(jnp.float32) * new_row(kn_ref, lanes),
            axis=1, keepdims=True), _NEG_INF),
         jnp.where(has_new, jnp.ones((gp, 1), jnp.float32), 0.0),
         jnp.where(has_new, jnp.broadcast_to(new_row(vn_ref, lanes), (gp, d)),
                   0.0))
        for q_j, lanes in zip(q_of, lanes_of))
    n_tiles = (num_kb - first_kb + tile - 1) // tile
    done = _two_block_dma_loop(n_tiles, copies, step, state0)
    for (_, l, acc), lanes in zip(done, lanes_of):
        o_ref[0, :, lanes] = (acc / jnp.maximum(l, 1e-30))[:members].astype(
            o_ref.dtype)


def _pair_members(q, h, g):
    """Grouped heads of 64 lanes as `_head_stream`'s rows.  A 128-lane
    tile t of a pool row holds K/V heads 2t and 2t + 1, and their 2 x G
    query heads are the tile's members: member p * G + m is query head
    (2t + p) * G + m, ZERO outside half p of the tile's lanes, so the
    tile's product `q [GP, 128] x K^T` gives each member its own head's
    scores, and `p x V [T, 128]` its own head's values in half p (the
    other half, the neighbour's values under this head's weights, is
    dropped by `_pair_outputs`).  q [B, Hq, 64] -> [B, GP, H * 64]."""
    b, _, d = q.shape
    qr = q.reshape(b, h // 2, 2, g, d)            # tile, half, member
    zero = jnp.zeros_like(qr[:, :, 0])
    own = jnp.concatenate(
        [jnp.stack([qr[:, :, 0], zero], axis=3),
         jnp.stack([zero, qr[:, :, 1]], axis=3)], axis=2)  # [B,t,2G,2,d]
    rows = jnp.swapaxes(own.reshape(b, h // 2, 2 * g, 2 * d), 1,
                        2).reshape(b, 2 * g, h * d)
    return jnp.pad(rows, ((0, 0), (0, _GROUP_ROWS - 2 * g), (0, 0)))


def _pair_outputs(o, h, g):
    """`_pair_members`' inverse on the kernel's output: member p * G + m
    of tile t keeps half p.  o [B, GP, H * 64] -> [B, Hq, 64]."""
    b = o.shape[0]
    d = o.shape[2] // h
    o6 = o[:, :2 * g].reshape(b, 2, g, h // 2, 2, d)
    kept = jnp.stack([o6[:, 0, :, :, 0], o6[:, 1, :, :, 1]],
                     axis=3)                      # [B, G, t, half, d]
    return kept.transpose(0, 2, 3, 1, 4).reshape(b, h * g, d)


# jitted on its own: a model's layers call it with the same shapes, and the
# kernel (the per-head body unrolls 16 or 32 heads, twice) is then traced
# and lowered once a program, not once a layer - 0.3 s a layer on the host
# of a v5e, which at 24 layers showed in a server's set-up (PERF.md, PR 29)
@functools.partial(jax.jit, static_argnames=("scale", "window", "interpret"))
def _ragged_kernel_call(q, k_new, v_new, k_blocks, v_blocks, block_table,
                        pos0, kv_lens, slots, k_scales, v_scales, scale,
                        window=None, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, h_q, d = q.shape
    nb, bs, hd = k_blocks.shape
    h = hd // d                    # K/V heads: the heads of a pool row
    g = h_q // h                   # query heads a K/V head
    quant = k_scales is not None
    pool_dt = k_blocks.dtype
    tbl = jnp.asarray(block_table, jnp.int32)
    maxb = int(tbl.shape[1])
    del pos0   # the kernel masks by kv_lens; pos0 == kv_lens - 1 at C=1
    lens_i = jnp.asarray(kv_lens, jnp.int32).reshape(b)
    slots_i = jnp.asarray(slots, jnp.int32).reshape(b)
    row = pl.BlockSpec((1, 1, hd), lambda r, *pre: (r, 0, 0))
    pool = pl.BlockSpec(memory_space=pltpu.HBM)
    heads = _head_products_ok(d, quant, g > 1)
    paired = heads and d % 128 != 0     # grouped heads of 64 lanes
    t_rows = max(1, _TILE_TOKENS // bs) * bs if heads else bs
    if g == 1:
        q_row, q_members = row, q.reshape(b, c, hd)
    elif paired:
        q_row = pl.BlockSpec((1, _GROUP_ROWS, hd), lambda r, *pre: (r, 0, 0))
        q_members = _pair_members(q.reshape(b, h_q, d), h, g)
    else:
        # query head j * G + m -> member m, lane segment j; the members
        # padded with zero rows to one sublane tile
        q_row = pl.BlockSpec((1, _GROUP_ROWS, hd), lambda r, *pre: (r, 0, 0))
        q_members = jnp.pad(
            jnp.swapaxes(q.reshape(b, h, g, d), 1, 2).reshape(b, g, hd),
            ((0, 0), (0, _GROUP_ROWS - g), (0, 0)))
    in_specs = [q_row, row, row, pool, pool]      # q, k_new, v_new, pools
    # the pools go in and come out as they are kept: a reshape of one
    # between [.., H, D] and [.., H*D] is a copy of all of it on a TPU
    args = [q_members, k_new.reshape(b, c, hd),
            v_new.reshape(b, c, hd), k_blocks, v_blocks]
    if quant:
        # grow the written blocks' scales here (the first half of
        # quantized_cache_update_arrays, bitwise: amax/qmax is monotone,
        # so a scatter-max of the quotients equals the quotient of the
        # max) and hand each row its target block's old and new scale;
        # the kernel streams against the NEW table
        valid = (slots_i >= 0) & (slots_i < nb * bs)
        blk = jnp.where(valid, slots_i // bs, nb)
        safe_blk = jnp.clip(blk, 0, nb - 1)
        safe_tbl = jnp.clip(tbl, 0, nb - 1)
        scale_row = pl.BlockSpec((1, 1, h), lambda r, *pre: (r, 0, 0))
        gathered = pl.BlockSpec((1, maxb, h), lambda r, *pre: (r, 0, 0))
        in_specs += [gathered, gathered]
        new_scales, row_scales = [], []
        for rows, scales in ((k_new, k_scales), (v_new, v_scales)):
            amax = jnp.max(jnp.abs(rows.reshape(b, h, d).astype(
                jnp.float32)), axis=-1)
            grown = scales.at[blk].max(amax / _QMAX, mode="drop")
            new_scales.append(grown)
            row_scales += [scales[safe_blk][:, None, :],
                           grown[safe_blk][:, None, :]]
            in_specs += [scale_row, scale_row]
        args += [jnp.take(new_scales[0], safe_tbl, axis=0),
                 jnp.take(new_scales[1], safe_tbl, axis=0)] + row_scales
    scratch = [
        pltpu.VMEM((2, 1, t_rows, hd), pool_dt),  # k stream, two tiles
        pltpu.VMEM((2, 1, t_rows, hd), pool_dt),  # v stream, two tiles
        pltpu.SemaphoreType.DMA((2, 2)),
        pltpu.VMEM((2, 1, bs, hd), pool_dt),      # target block k/v
        pltpu.SemaphoreType.DMA((2,)),
    ]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=in_specs,
        out_specs=[q_row, pool, pool],
        scratch_shapes=scratch,
    )
    # a pair of 64-lane heads is, to the stream, one head of a lane tile
    kernel = functools.partial(
        _ragged_fused_kernel, bs=bs, h=h // 2 if paired else h,
        d=2 * d if paired else d, nb=nb, maxb=maxb, scale=scale,
        quant=quant, window=window, heads=heads)
    # aliasing indices INCLUDE the scalar-prefetch args (lens=0, slots=1,
    # tables=2, q=3, k_new=4, v_new=5, pools=6/7)
    outs = pl.pallas_call(
        kernel,
        name="ragged_paged_attention",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q_members.shape, q.dtype),
                   jax.ShapeDtypeStruct((nb, bs, hd), pool_dt),
                   jax.ShapeDtypeStruct((nb, bs, hd), pool_dt)],
        input_output_aliases={6: 1, 7: 2},
        interpret=interpret,
    )(lens_i, slots_i, tbl, *args)
    if g == 1:
        o = outs[0].reshape(b, c, h_q, d)
    elif paired:
        o = _pair_outputs(outs[0], h, g).reshape(b, c, h_q, d)
    else:
        o = jnp.swapaxes(outs[0][:, :g].reshape(b, g, h, d), 1,
                         2).reshape(b, c, h_q, d)
    k2, v2 = outs[1], outs[2]
    if quant:
        return o, k2, v2, new_scales[0], new_scales[1]
    return o, k2, v2


# ---------------------------------------------------------------------------
# XLA array-level fallback pieces
# ---------------------------------------------------------------------------

def _folded_quant_attention(q, k_blocks, v_blocks, k_scales, v_scales,
                            block_table, pos0, scale):
    """int8 paged attention WITHOUT the dequantizing gather: int8 CODES
    are gathered (¼ of the fp32 dequant materialization
    `quantized_gather_kv_arrays` pays) and the per-block-per-head
    scales fold into the logits (K side) and probabilities (V side) —
    exact in real arithmetic because the scale is constant along the
    contracted head_dim axis."""
    b, s, h, d = q.shape
    nb, bs, _ = k_blocks.shape
    tbl = jnp.clip(jnp.asarray(block_table, jnp.int32), 0, nb - 1)
    maxb = tbl.shape[1]
    s_pad = maxb * bs
    kg = jnp.take(k_blocks, tbl, axis=0).reshape(b, s_pad, h, d)
    vg = jnp.take(v_blocks, tbl, axis=0).reshape(b, s_pad, h, d)
    # per-position scales: [B, maxb, H] broadcast over the block rows —
    # [B, S_pad, H] fp32, a D-th of the dequantized-KV footprint
    ksg = jnp.broadcast_to(
        jnp.take(k_scales, tbl, axis=0)[:, :, None, :],
        (b, maxb, bs, h)).reshape(b, s_pad, h)
    vsg = jnp.broadcast_to(
        jnp.take(v_scales, tbl, axis=0)[:, :, None, :],
        (b, maxb, bs, h)).reshape(b, s_pad, h)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kg.astype(jnp.float32),
                        preferred_element_type=jnp.float32) * scale
    logits = logits * jnp.transpose(ksg, (0, 2, 1))[:, :, None, :]
    q_pos = jnp.asarray(pos0, jnp.int32)[:, None] + jnp.arange(
        s, dtype=jnp.int32)[None, :]
    k_pos = jnp.arange(s_pad, dtype=jnp.int32)
    causal = k_pos[None, None, :] <= q_pos[:, :, None]
    logits = jnp.where(causal[:, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    pw = probs * jnp.transpose(vsg, (0, 2, 1))[:, :, None, :]
    out = jnp.einsum("bhqk,bkhd->bqhd", pw, vg.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def ragged_paged_attention_arrays(q, k_new, v_new, k_blocks, v_blocks,
                                  block_table, pos0, kv_lens, slots,
                                  k_scales=None, v_scales=None, scale=None,
                                  window=None):
    """Fused cache-update + causal paged attention for a ragged batch in
    ONE fixed-shape program.

    q, k_new, v_new: [B, C, H, D] — the current tokens (C = 1 at decode;
                     C > 1 for a prefill-continuation chunk, or a
                     speculative-decode VERIFY batch: position 0 is the
                     row's last real token and positions 1..k its draft
                     tokens).  Rows may sit at DIFFERENT absolute
                     positions (mixed prefill/decode batches) and
                     padding rides along at BOTH granularities: whole
                     padding rows AND, in a verify batch, a row's unused
                     trailing draft positions — either way a dropped
                     slot suppresses the write and the caller ignores
                     the output.  Write-then-attend makes in-chunk
                     causality the pool's own: draft j's query sees
                     draft j-1's K/V because the update lands before the
                     attention reads, under the same per-position causal
                     mask as sequential decode — which is what lets the
                     engine score all k+1 positions in ONE launch and
                     stay token-identical to step-by-step greedy.
    k_blocks/v_blocks: [num_blocks, block_size, H*D] physical pools
                     (fp, or int8 codes with `k_scales`/`v_scales`
                     [num_blocks, H] per-block-per-head scale pools);
                     the kernel takes and returns them as they are,
                     aliased in place.
    block_table:     [B, max_blocks] int32 per-row logical→physical map.
    pos0:            [B] int32 absolute position of each row's first
                     query (== context length before this chunk).
    kv_lens:         [B] int32 valid KEY count per row AFTER the write
                     (pos0 + valid queries) — the kernel's block-loop
                     bound; ignored by the masked fallback.
    slots:           [B, C] int32 physical write slots; out-of-range
                     entries (padding / evicted rows) are dropped.
    window:          optional int: a key is visible iff it lies fewer
                     than `window` positions behind the query (a sliding-
                     window layer).  The kernel then starts its stream at
                     the window's first block; table entries wholly
                     behind the window may point nowhere.

    Grouped heads: k_new/v_new and the pools may hold fewer heads than q
    ([B, C, H_kv, D], rows of H_kv * D); query head h reads K/V head
    h // (H / H_kv).  Full precision only.

    Returns ``(out, k_blocks', v_blocks')`` — plus ``(k_scales',
    v_scales')`` in quantized mode.  The new tokens' K/V are written to
    their slots INSIDE the program (write-then-attend, the dense cache
    ordering), so callers never run a separate cache-update pass.
    """
    b, c, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    quant = k_scales is not None
    if quant != (v_scales is not None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    if quant and (window is not None
                  or int(k_blocks.shape[2]) != h * d):
        raise ValueError("int8 pools are read with as many K/V heads as "
                         "query heads and no window")
    if _ragged_kernel_ok(q, k_blocks, c, quant, window):
        return _ragged_kernel_call(q, k_new, v_new, k_blocks, v_blocks,
                                   block_table, pos0, kv_lens, slots,
                                   k_scales, v_scales, scale, window,
                                   interpret=_interpret())
    if not quant:
        # bitwise the reference composition — the fp parity contract
        k2 = paged_cache_update_arrays(k_blocks, k_new, slots)
        v2 = paged_cache_update_arrays(v_blocks, v_new, slots)
        out = paged_attention_arrays(q, k2, v2, block_table, pos0,
                                     scale=scale, window=window)
        return out, k2, v2
    k2, ks2 = quantized_cache_update_arrays(k_blocks, k_scales, k_new,
                                            slots)
    v2, vs2 = quantized_cache_update_arrays(v_blocks, v_scales, v_new,
                                            slots)
    out = _folded_quant_attention(q, k2, v2, ks2, vs2, block_table, pos0,
                                  scale)
    return out, k2, v2, ks2, vs2


# ---------------------------------------------------------------------------
# latent rows: one pool a layer, every query head reads the same row, as
# its key and (the leading lanes) as its value
# ---------------------------------------------------------------------------

# tokens of latents one step of the latent stream consumes: 1,024 rows of
# 384 lanes are 768 KB, 16 copies of a 64-row block (PR 39, my chip runs
# A-B: at 256 rows a tile's products cost 0.42 us a 256 rows, at 1,024 0.19)
_LATENT_TILE_TOKENS = 1024
# tiles of the latent stream a program holds at once: tile t + RING - 1's
# copies are in flight while tile t's products run
_LATENT_RING = 3


def _ring_dma_loop(num_t, copies, step, carry, depth):
    """carry = step(slot, t, carry) for t in [0, num_t), each tile's DMAs
    started `depth - 1` tiles AHEAD of its products, across loop
    iterations: the copies of tile t + depth - 1 (into the buffer tile
    t - 1 left) start at the top of iteration t, before `step` waits on
    tile t.  Every tile is started once and waited on once, so no copy is
    in flight when the program ends.  `slot` is traced here (t % depth),
    unlike `_two_block_dma_loop`'s."""
    from jax.experimental import pallas as pl

    def start(t):
        for c in copies(t % depth, t):
            c.start()

    for t in range(depth - 1):
        pl.when(t < num_t)(functools.partial(start, t))

    def body(t, carry):
        pl.when(t + depth - 1 < num_t)(lambda: start(t + depth - 1))
        return step(t % depth, t, carry)

    return jax.lax.fori_loop(0, num_t, body, carry)


def _latent_kernel_ok(q, pool, c, value_dim) -> bool:
    """Geometry/flag gate of the latent decode kernel, counted like
    `_ragged_kernel_ok`."""
    if not _decode_kernel_wanted(c):
        return False
    bs, lanes = int(pool.shape[1]), int(pool.shape[2])
    if lanes % 128 or value_dim % 128 or not value_dim <= q.shape[-1] <= lanes:
        # the value is sliced out of a loaded tile of rows: whole lane
        # tiles, inside the key
        _count_path("ragged_fallback:latent_geometry")
        return False
    if bs % (16 if pool.dtype == jnp.bfloat16 else 8):
        _count_path("ragged_fallback:block_size")
        return False
    if q.dtype != pool.dtype:
        _count_path("ragged_fallback:dtype_mix")
        return False
    _count_path("ragged_kernel")
    _count_path("ragged_kernel:latent_products")
    return True


def _latent_kernel(len_ref, slot_ref, tbl_ref, q_ref, rn_ref, p_hbm, o_ref,
                   po_hbm, buf, sem, ublk, usem, *, bs, dv, nb, maxb, scale):
    """One program per batch row r, `_ragged_fused_kernel`'s two steps
    over ONE pool.

    1. The row's target block is DMA'd in, the new latent `rn_ref`
       `[1, 1, lanes]` spliced in at its offset, and DMA'd back (the pool
       is aliased in place; an out-of-range slot skips the write).
    2. The row's latents stream from HBM through its block table, a TILE
       of `buf.shape[2] // bs` blocks a step gathered by as many DMAs into
       one `[T, lanes]` buffer that is read TWICE: `q~ [HP, lanes] x
       rows^T` gives every query head's scores (the lanes past the key
       hold zeros on both sides), `p [HP, T] x rows[:, :dv]` their sums,
       with the online-softmax state m, l `[HP, 1]` and acc `[HP, dv]` in
       float32.  As in `_head_stream`, the row's NEW latent is the state
       the stream starts from and position `length - 1` is masked in what
       is streamed; entries of the last tile past the row's last block
       fetch that last block again, every position of theirs masked.
       The tiles go through a ring of `buf.shape[0]` buffers
       (`_ring_dma_loop`): a tile's copies are in flight while the
       products of the tiles before it run.

    q_ref/o_ref hold the query heads as rows, padded with zero rows to
    whole sublane tiles (HP)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = pl.program_id(0)
    length = jnp.maximum(len_ref[r], 0)
    slot = slot_ref[r]
    valid = (slot >= 0) & (slot < nb * bs)
    blk = jnp.clip(slot // bs, 0, nb - 1)
    off = jnp.where(valid, slot % bs, 0)

    rd = pltpu.make_async_copy(p_hbm.at[pl.ds(blk, 1)], ublk, usem.at[0])
    rd.start()
    rd.wait()
    off_mask = (jax.lax.broadcasted_iota(jnp.int32, (1, bs, 1), 1) == off)
    ublk[...] = jnp.where(off_mask & valid, rn_ref[...].astype(ublk.dtype),
                          ublk[...])

    @pl.when(valid)
    def _writeback():
        wr = pltpu.make_async_copy(ublk, po_hbm.at[pl.ds(blk, 1)],
                                   usem.at[0])
        wr.start()
        wr.wait()       # before the stream may read the same region

    q = q_ref[0]                                          # [HP, lanes]
    hp = q.shape[0]
    t_rows = buf.shape[2]
    tile = t_rows // bs
    has_new = valid & (length > 0)
    n_old = jnp.where(has_new, length - 1, length)
    num_kb = jnp.minimum((n_old + bs - 1) // bs, maxb)

    def copies(slot_i, t):
        dmas = []
        for i in range(tile):
            kb = jnp.minimum(t * tile + i, num_kb - 1)
            b_kb = jnp.clip(tbl_ref[r, kb], 0, nb - 1)
            dmas.append(pltpu.make_async_copy(
                p_hbm.at[pl.ds(b_kb, 1)],
                buf.at[slot_i, :, pl.ds(i * bs, bs)], sem.at[slot_i]))
        return dmas

    def step(sl, t, carry):
        m, l, acc = carry
        for c in copies(sl, t):
            c.wait()
        rows = buf[sl, 0]                                 # [T, lanes]
        s = _dot_f32(q, rows, transpose_b=True) * scale   # [HP, T]
        pos = t * t_rows + jax.lax.broadcasted_iota(
            jnp.int32, (hp, t_rows), 1)
        s = jnp.where(pos < n_old, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                acc * alpha + _dot_f32(p.astype(rows.dtype), rows[:, :dv]))

    def new_row(lanes):            # [1, n] float32, as the pool keeps it
        return rn_ref[0, :, lanes].astype(buf.dtype).astype(jnp.float32)

    state0 = (
        jnp.where(has_new, scale * jnp.sum(
            q.astype(jnp.float32) * new_row(slice(None)), axis=1,
            keepdims=True), _NEG_INF),
        jnp.where(has_new, jnp.ones((hp, 1), jnp.float32), 0.0),
        jnp.where(has_new, jnp.broadcast_to(new_row(slice(0, dv)),
                                            (hp, dv)), 0.0))
    n_tiles = (num_kb + tile - 1) // tile
    _, l, acc = _ring_dma_loop(n_tiles, copies, step, state0, buf.shape[0])
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("value_dim", "scale",
                                             "interpret"))
def _latent_kernel_call(q, row_new, pool, block_table, kv_lens, slots,
                        value_dim, scale, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, _, h, dk = q.shape
    nb, bs, lanes = pool.shape
    hp = -(-h // _GROUP_ROWS) * _GROUP_ROWS
    tbl = jnp.asarray(block_table, jnp.int32)
    t_rows = max(1, _LATENT_TILE_TOKENS // bs) * bs
    q_rows = jnp.pad(q.reshape(b, h, dk),
                     ((0, 0), (0, hp - h), (0, lanes - dk)))
    new = jnp.pad(row_new.reshape(b, 1, dk), ((0, 0), (0, 0),
                                              (0, lanes - dk)))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[pl.BlockSpec((1, hp, lanes), lambda r, *pre: (r, 0, 0)),
                  pl.BlockSpec((1, 1, lanes), lambda r, *pre: (r, 0, 0)),
                  hbm],
        out_specs=[pl.BlockSpec((1, hp, value_dim),
                                lambda r, *pre: (r, 0, 0)), hbm],
        scratch_shapes=[
            pltpu.VMEM((_LATENT_RING, 1, t_rows, lanes), pool.dtype),
            pltpu.SemaphoreType.DMA((_LATENT_RING,)),
            pltpu.VMEM((1, bs, lanes), pool.dtype),          # target block
            pltpu.SemaphoreType.DMA((1,)),
        ],
    )
    # aliasing indices INCLUDE the scalar-prefetch args (lens=0, slots=1,
    # tables=2, q=3, new row=4, pool=5)
    o, pool2 = pl.pallas_call(
        functools.partial(_latent_kernel, bs=bs, dv=value_dim, nb=nb,
                          maxb=int(tbl.shape[1]), scale=scale),
        name="ragged_latent_attention",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hp, value_dim), q.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={5: 1},
        interpret=interpret,
    )(jnp.asarray(kv_lens, jnp.int32).reshape(b),
      jnp.asarray(slots, jnp.int32).reshape(b), tbl, q_rows, new, pool)
    return o[:, :h].reshape(b, 1, h, value_dim), pool2


def ragged_latent_attention_arrays(q, row_new, pool, block_table, pos0,
                                   kv_lens, slots, value_dim, scale):
    """`ragged_paged_attention_arrays` over a latent pool: the current
    tokens' latent rows written, then causal attention of the absorbed
    queries against the rows, in ONE fixed-shape program.

    q:        [B, C, H, key_dim] absorbed queries (every head scores
              against the same row)
    row_new:  [B, C, key_dim] the current tokens' latent rows
    pool:     [num_blocks, block_size, latent_pool_lanes(key_dim)]
    block_table, pos0, kv_lens, slots: as `ragged_paged_attention_arrays`
    value_dim: a row's leading lanes that are its value
    -> (out [B, C, H, value_dim], pool')."""
    c = q.shape[1]
    if _latent_kernel_ok(q, pool, c, value_dim):
        return _latent_kernel_call(q, row_new, pool, block_table, kv_lens,
                                   slots, value_dim, scale,
                                   interpret=_interpret())
    pool2 = latent_cache_update_arrays(pool, row_new, slots)
    return latent_paged_attention_arrays(q, pool2, block_table, pos0,
                                         value_dim, scale), pool2
