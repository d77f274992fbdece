"""Paged-KV-cache attention (array level) — the serving-side primitives of
`paddle_tpu.serving` (Ragged Paged Attention, PAPERS.md: block-paged KV
caches + ragged batch decoding are the TPU-side key to high-throughput LLM
serving): the pool writers the engine's prefill program calls, and the
gather + masked attention that `ops.ragged_paged_attention` composes into
its XLA fallback and that its tests hold it to.

Layout: K/V live in fixed-size physical blocks, heads flattened in the
last axis

    k_blocks, v_blocks : [num_blocks, block_size, num_heads * head_dim]

and each sequence owns a *block table* row mapping its logical blocks to
physical ones.  Token `p` of a sequence lives at physical slot
``table[p // block_size] * block_size + p % block_size``.  Every function
here takes pools of that rank only.  It is the shape the ragged kernel
DMAs (`ops.ragged_paged_attention`); a TPU tiles an array over its last
two axes, so splitting ``H*D -> (H, D)`` on a POOL is a copy of the whole
pool, and the readers below split the rows they gathered instead.  The
writers move whole blocks for the same reason (`_block_window`): a block
is whole tiles when `block_size` is a multiple of the pool dtype's sublane
tile (8 f32 / 16 bf16 / 32 int8), one token's row never is.

Numerics contract: `paged_attention_arrays` reproduces the masked-softmax
decode path of `cached_attention_arrays` (models/gpt.py:326 is the
numerical reference) EXACTLY — same einsum contraction (fp32
accumulation), same additive -1e30 causal mask, same softmax and
probs-cast — so paged decode is token-for-token identical to the dense
`[B, S_max]` ring decode: gathered block rows land at the same logical
key positions, and padding rows beyond a row's context are masked to an
exact 0 probability (exp underflows to 0.0), contributing exactly nothing
to the reductions.  tests/test_serving.py pins this parity against
`GPTModel.generate()`.

Latent rows (ISSUE 34, latent attention): a layer may keep ONE pool whose
row is a token's latent - `key_dim` numbers that every query head scores
against, the first `value_dim` of them also the value every head sums
(`latent_cache_update_arrays`, `latent_paged_attention_arrays`).  The pool
is `latent_pool_lanes(key_dim)` wide, whole lane tiles, zeros past
`key_dim`: a DMA moves whole tiles, and a row of 320 takes three of them in
HBM however it is declared.

No Pallas kernel here yet: at S_q = 1 the op is bandwidth-bound (MXU
irrelevant), matching the dense decode path's design note; a fused
gather+attention kernel is the obvious follow-up once serving shapes are
profiled on chip.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["paged_attention_arrays", "paged_cache_update_arrays",
           "paged_gather_kv_arrays", "slot_mapping",
           "quantized_cache_update_arrays", "quantized_gather_kv_arrays",
           "latent_pool_lanes", "latent_cache_update_arrays",
           "latent_paged_attention_arrays"]

_NEG_INF = -1e30


def slot_mapping(block_table, positions, block_size, num_slots,
                 valid=None):
    """Physical slot of each (row, position): ``[B, S]`` int32.

    block_table: [B, max_blocks] int32 physical block ids (rows may be
    padded arbitrarily past the blocks a sequence owns — positions only
    index into the table through ``positions // block_size``).
    positions:   [B, S] int32 absolute token positions.
    valid:       optional [B, S] bool; invalid entries map to `num_slots`
    (one past the last slot) so a scatter with mode='drop' discards them.
    """
    block_table = jnp.asarray(block_table, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    bs = int(block_size)
    logical = positions // bs
    maxb = block_table.shape[1]
    phys = jnp.take_along_axis(
        block_table, jnp.clip(logical, 0, maxb - 1), axis=1)
    slots = phys * bs + positions % bs
    if valid is not None:
        slots = jnp.where(valid, slots, jnp.int32(num_slots))
    return slots


def _block_window(rows, slots, bs, nb):
    """Cut each row's tokens into the blocks they land in, so a writer
    moves whole ``[block_size, F]`` slabs: with heads flattened a token's
    row is ONE sublane of F/128 tiles, and a scatter of S such part-tile
    rows cost a prefill a sixth more on the chip (PERF.md, PR 27).

    rows [B, S, F], slots [B, S] as `slot_mapping` makes them for S
    consecutive positions: the in-range slots of a row are a PREFIX of it
    and walk its blocks in order (`_check_consecutive`).
    With ``W = (S + bs - 2) // bs + 1`` blocks that many tokens can touch:

    ids  [B, W]         block each window block is (nb: nothing lands, the
                        scatter drops it)
    win  [B, W, bs, F]  the rows, each at its offset in its block
    mask [B, W, bs]     where a token lands; elsewhere the block keeps
                        what it held
    """
    b, s = slots.shape
    slots = jnp.asarray(slots, jnp.int32)
    n = jnp.sum((slots >= 0) & (slots < nb * bs), axis=1)        # [B]
    off = jnp.where(n > 0, slots[:, 0] % bs, 0)
    w = (s + bs - 2) // bs + 1
    tok = jnp.arange(w * bs, dtype=jnp.int32)[None] - off[:, None]
    mask = ((tok >= 0) & (tok < n[:, None])).reshape(b, w, bs)
    head = jnp.clip(tok[:, ::bs], 0, s - 1)      # first token of a block
    ids = jnp.where(mask.any(axis=2),
                    jnp.take_along_axis(slots, head, axis=1) // bs, nb)
    padded = jnp.pad(rows, ((0, 0), (bs - 1, w * bs - s), (0, 0)))
    win = jax.vmap(lambda p, o: jax.lax.dynamic_slice_in_dim(
        p, bs - 1 - o, w * bs, axis=0))(padded, off)
    return ids, win.reshape(b, w, bs, -1), mask


def _check_consecutive(slots, bs, num_slots):
    """What `_block_window` takes for granted, checked where it can be:
    an eager caller's concrete slots (the engine's are traced)."""
    import numpy as np

    if isinstance(slots, jax.core.Tracer):
        return
    # ptpu-check[host-sync]: never reached under a trace (returned above)
    sl = np.asarray(slots)
    ok = (sl >= 0) & (sl < num_slots)
    step = np.where(sl[:, :-1] % bs == bs - 1, sl[:, 1:] % bs == 0,
                    sl[:, 1:] == sl[:, :-1] + 1)
    if (ok[:, 1:] & ~ok[:, :-1]).any() or (ok[:, 1:] & ~step).any():
        raise ValueError(
            "a row's slots must be those of consecutive positions, the "
            "out-of-range ones last (slot_mapping's): the pool is written "
            "a block at a time")


def paged_cache_update_arrays(blocks, rows, slots):
    """Write new K (or V) rows into the paged pool.

    blocks: [num_blocks, block_size, H*D]
    rows:   [B, S, H, D] (or [B, S, H*D]) new keys/values
    slots:  [B, S] int32 physical slots of S consecutive positions (from
            `slot_mapping`); out-of-range entries (padding / inactive
            rows, last in a row) are DROPPED, never clamped — a clamp
            would silently corrupt the last block.
    Returns the updated pool (same shape/dtype as `blocks`): only the
    slots named change, but the device reads, merges and writes the
    touched blocks whole (`_block_window`).
    """
    nb, bs, _ = blocks.shape
    _check_consecutive(slots, bs, nb * bs)
    if isinstance(blocks, jax.core.Tracer) and not isinstance(
            slots, jax.core.Tracer):
        # slots closed over by the caller's jit (the engine's are traced):
        # where the rows are constants of the program too, the TPU
        # compiler folds `_block_window`'s slices of constant rows at
        # constant offsets to ZEROS (the optimised HLO for a described v5e
        # holds the broadcast; the CPU's is right; PERF.md, PR 34).  Behind
        # a barrier the offsets are a run-time value and the slices taken.
        slots = jax.lax.optimization_barrier(jnp.asarray(slots, jnp.int32))
    return _update(blocks, rows, slots)


@jax.jit    # a program writes 2 pools a layer: traced once, not 2L times
def _update(blocks, rows, slots):
    nb, bs, hd = blocks.shape
    b, s = slots.shape
    ids, win, mask = _block_window(
        rows.reshape(b, s, hd).astype(blocks.dtype), slots, bs, nb)
    merged = jnp.where(mask[..., None], win,
                       blocks[jnp.clip(ids, 0, nb - 1)])
    return blocks.at[ids.reshape(-1)].set(
        merged.reshape(-1, bs, hd), mode="drop")


def paged_gather_kv_arrays(blocks, block_table, num_heads):
    """Gather one sequence-major view of the [num_blocks, block_size,
    H*D] pool: [B, max_blocks * block_size, H, D] (heads split on the
    gathered rows).  Rows past a sequence's context hold garbage (stale
    or zero blocks) — callers mask them; table entries are clipped into
    range (padding entries gather *some* block, masked the same way)."""
    nb, bs, hd = blocks.shape
    tbl = jnp.clip(jnp.asarray(block_table, jnp.int32), 0, nb - 1)
    g = jnp.take(blocks, tbl, axis=0)          # [B, maxb, bs, H*D]
    b, maxb = tbl.shape
    return g.reshape(b, maxb * bs, num_heads, hd // num_heads)


def quantized_cache_update_arrays(blocks, scales, rows, slots, qmax=127):
    """Scatter new K (or V) rows into an int8 paged pool with
    per-block-per-head abs-max scales (the `lowbit` KV wing).

    blocks: int8 [num_blocks, block_size, H*D] codes
    scales: f32  [num_blocks, H] — ``value = code * scale``
    rows:   [B, S, H, D] float K/V rows to write
    slots:  [B, S] int32 physical slots; out-of-range (padding) entries
            are dropped exactly like `paged_cache_update_arrays`.

    A block's scale only ever GROWS (amax of everything written since the
    block was taken — the allocator resets scales on reallocation).  When
    an incoming row raises a block's amax, that block's existing codes
    are rescaled ``round(q · old/new)`` — one extra rounding, bounded by
    half an int8 step at the new scale.  When the scale is unchanged the
    rescale factor is exactly 1.0 and the codes pass through bit-stable
    (int8→f32→round is exact), which is what keeps steady-state decode
    deterministic.

    Returns (blocks', scales').
    """
    nb, bs, _ = blocks.shape
    _check_consecutive(slots, bs, nb * bs)
    return _quantized_update(blocks, scales, rows, slots, qmax)


@functools.partial(jax.jit, static_argnums=(4,))
def _quantized_update(blocks, scales, rows, slots, qmax):
    nb, bs, hd = blocks.shape
    h = scales.shape[1]
    d = hd // h
    b, s = slots.shape
    ids, win, mask = _block_window(
        rows.reshape(b, s, hd).astype(jnp.float32), slots, bs, nb)
    w = ids.shape[1]
    win = win.reshape(b, w, bs, h, d)       # heads split on the written
    lands = mask[..., None, None]           # blocks, never on the pool
    # per-(block, head) abs-max of the incoming rows grows the scale
    amax = jnp.max(jnp.where(lands, jnp.abs(win), 0.0), axis=(2, 4))
    new_scales = scales.at[ids.reshape(-1)].max(
        (amax / qmax).reshape(-1, h), mode="drop")
    # the written blocks are the only ones whose scale can have changed:
    # gather -> rescale -> merge the quantized rows in -> scatter back, at
    # block granularity.  O(written blocks), not O(pool), so XLA mutates
    # the donated pool in place.  Ids of nb gather clipped garbage that
    # the mode="drop" scatter discards.
    gid = jnp.clip(ids, 0, nb - 1)
    new = new_scales[gid]                            # [B, W, H]
    new = jnp.where(new > 0, new, 1.0)
    factor = jnp.where(new_scales[gid] > 0, scales[gid] / new, 1.0)
    rescaled = jnp.round(blocks[gid].reshape(b, w, bs, h, d).astype(
        jnp.float32) * factor[:, :, None, :, None])
    # quantize the incoming rows against their block's (new) scale
    q_rows = jnp.round(win / new[:, :, None, :, None])
    merged = jnp.clip(jnp.where(lands, q_rows, rescaled),
                      -qmax, qmax).astype(jnp.int8)
    return blocks.at[ids.reshape(-1)].set(
        merged.reshape(-1, bs, hd), mode="drop"), new_scales


def quantized_gather_kv_arrays(blocks, scales, block_table):
    """Dequantizing gather: the int8 analog of `paged_gather_kv_arrays`,
    returning float32 [B, max_blocks * block_size, H, D] =
    ``codes * per-block-per-head scale``.

    This IS the separate dequant pass (a 4-byte fp32 materialization of
    the 1-byte pool) that `paged_attention_arrays` pays under int8;
    `ops.ragged_paged_attention` exists to not call it — the counter
    below is how the bench/tests pin that (ISSUE 8 acceptance: no
    ``site="paged_gather"`` increments on the ragged path)."""
    from .lowbit import _count

    _count("lowbit/dequant_calls", site="paged_gather")
    nb, bs, hd = blocks.shape
    h = scales.shape[1]
    d = hd // h
    tbl = jnp.clip(jnp.asarray(block_table, jnp.int32), 0, nb - 1)
    b, maxb = tbl.shape
    g = jnp.take(blocks, tbl, axis=0)                # [B, maxb, bs, H*D]
    s = jnp.take(scales, tbl, axis=0)                # [B, maxb, H]
    deq = g.reshape(b, maxb, bs, h, d).astype(jnp.float32) \
        * s[:, :, None, :, None]
    return deq.reshape(b, maxb * bs, h, d)


def paged_attention_arrays(q, k_blocks, v_blocks, block_table, pos0,
                           scale=None, k_scales=None, v_scales=None,
                           window=None):
    """Causal attention of a (ragged) batch against its paged KV cache.

    q:            [B, S, H, D] — S=1 at decode, >1 for a prefill chunk
    k_blocks/v_blocks: [num_blocks, block_size, H*D] physical pools
                  (the current chunk's K/V must already be written —
                  write-then-attend, like the dense cache path)
    block_table:  [B, max_blocks] int32 per-row logical→physical map
    pos0:         [B] int32 absolute position of each row's FIRST query
                  (== that row's context length before this chunk)
    Returns [B, S, H, D] in q's dtype.

    Each query at absolute position p attends keys with k_pos <= p —
    the same additive -1e30 mask + fp32-softmax arithmetic as
    `cached_attention_arrays`, with a per-ROW position instead of its
    scalar `t` (that is the whole ragged-batch generalization).

    k_scales/v_scales: pass the [num_blocks, H] per-block-per-head scale
    pools to read int8-quantized K/V blocks (the lowbit KV wing) — the
    gather dequantizes, the attention arithmetic is unchanged.

    Grouped heads: the pools may hold FEWER heads than q (rows of
    `H_kv * D`, H a multiple of H_kv); query head h reads K/V head
    h // (H / H_kv).  `window`: a key is visible iff it lies fewer than
    `window` positions behind the query; table entries wholly behind
    every query's window may point nowhere (they gather some block, and
    this mask covers it).
    """
    b, s, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    h_kv = int(k_blocks.shape[2]) // d
    if k_scales is not None:
        # lowbit path: int8 pools + per-block-per-head scales dequantize
        # inside the gather; the attention arithmetic below is unchanged
        kg = quantized_gather_kv_arrays(k_blocks, k_scales, block_table)
        vg = quantized_gather_kv_arrays(v_blocks, v_scales, block_table)
    else:
        kg = paged_gather_kv_arrays(k_blocks, block_table, h_kv)
        vg = paged_gather_kv_arrays(v_blocks, block_table, h_kv)
    s_pad = kg.shape[1]
    q_pos = jnp.asarray(pos0, jnp.int32)[:, None] + jnp.arange(
        s, dtype=jnp.int32)[None, :]                       # [B, S]
    k_pos = jnp.arange(s_pad, dtype=jnp.int32)
    seen = k_pos[None, None, :] <= q_pos[:, :, None]       # [B, S, S_pad]
    if window is not None:
        seen &= q_pos[:, :, None] - k_pos[None, None, :] < window
    if h_kv != h:
        qg = q.reshape(b, s, h_kv, h // h_kv, d)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kg,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(seen[:, None, None], logits, _NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(vg.dtype), vg)
        return out.reshape(b, s, h, d).astype(q.dtype)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kg,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(seen[:, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(vg.dtype), vg)
    return out.astype(q.dtype)


# -- latent rows: one pool a layer, the value inside the key ----------------

def latent_pool_lanes(key_dim) -> int:
    """Width of a latent pool's row: `key_dim` rounded up to whole lane
    tiles (320 -> 384)."""
    return -(-int(key_dim) // 128) * 128


def latent_cache_update_arrays(pool, rows, slots):
    """Write latent rows [B, S, key_dim] into the pool [num_blocks,
    block_size, lanes >= key_dim], zeros in the lanes past `key_dim`;
    `slots` as `paged_cache_update_arrays` takes them."""
    pad = int(pool.shape[2]) - int(rows.shape[-1])
    return paged_cache_update_arrays(
        pool, jnp.pad(rows, ((0, 0), (0, 0), (0, pad))), slots)


def latent_paged_attention_arrays(q, pool, block_table, pos0, value_dim,
                                  scale):
    """Causal attention of absorbed queries against a latent pool.

    q:     [B, S, H, key_dim]: every head scores against the SAME row
    pool:  [num_blocks, block_size, lanes >= key_dim] (the chunk's rows
           already written: write-then-attend)
    -> [B, S, H, value_dim]: each head's weights over the rows' first
       `value_dim` lanes.  The masked-softmax arithmetic is
       `paged_attention_arrays`': additive -1e30 mask over the whole
       padded extent, float32 softmax, weights cast to the pool's type.
    """
    b, s, h, dk = q.shape
    rows = paged_gather_kv_arrays(pool, block_table, 1)[:, :, 0]
    s_pad = rows.shape[1]
    q_pos = jnp.asarray(pos0, jnp.int32)[:, None] + jnp.arange(
        s, dtype=jnp.int32)[None, :]                       # [B, S]
    k_pos = jnp.arange(s_pad, dtype=jnp.int32)
    seen = k_pos[None, None, :] <= q_pos[:, :, None]       # [B, S, S_pad]
    logits = jnp.einsum("bqhd,bkd->bhqk", q, rows[..., :dk],
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(seen[:, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkd->bqhd", probs.astype(rows.dtype),
                     rows[..., :value_dim])
    return out.astype(q.dtype)
