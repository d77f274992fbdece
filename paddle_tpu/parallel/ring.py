"""Ring attention — sequence/context parallelism over the 'sp' mesh axis.

Capability gap the reference snapshot leaves open (SURVEY §5.7: no ring
attention / context parallel / Ulysses anywhere; long sequences are handled
only by recompute). Built natively here because long-context GPT pretrain
is table stakes for the north-star config: the sequence stays sharded
through attention, and K/V blocks rotate around the 'sp' ring via
`lax.ppermute` (one ICI hop per step) while each device accumulates its
queries' output with an online (flash-style) softmax. Peak memory per chip
is O(S/n · S/n) attention scores instead of O(S · S), and compute/comm
overlap rides XLA's latency-hiding scheduler.

Layouts match ops/pallas_ops.py: q, k, v are [B, S, H, D].
"""
from __future__ import annotations

import math
import warnings
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.dispatch import apply
from .mesh import get_mesh, axis_size

__all__ = ["ring_attention", "ring_attention_arrays", "zigzag_sequence_perm"]


def _online_block_update(carry, q_scaled, qpos, k_blk, v_blk, kpos,
                         qseg=None, kseg=None):
    """One flash-style online-softmax accumulation of a K/V block against
    scaled queries (shared by the contiguous and zigzag ring bodies — the
    numerically delicate part lives exactly once). kpos=None means no
    causal mask for this block; qseg/kseg ([B, Sq]/[B, Sk] int32) add
    packed-segment masking (positions attend iff ids match — safe with
    the diagonal-first visit order: a row's own position always matches
    its own segment, so m turns finite before foreign blocks arrive)."""
    o, m, l = carry
    s = jnp.einsum("bqhd,bkhd->bhqk", q_scaled, k_blk.astype(jnp.float32))
    if kpos is not None:
        s = jnp.where(kpos[None, None, None, :]
                      > qpos[None, None, :, None], -jnp.inf, s)
    if qseg is not None:
        s = jnp.where(qseg[:, None, :, None] == kseg[:, None, None, :],
                      s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # rows whose running max is still -inf (every block seen so far fully
    # masked — segment masking can order a fully-masked pair before the
    # diagonal one) must contribute exact zeros, not exp(-inf - -inf)=NaN
    p = jnp.where(jnp.isneginf(m_new)[..., None], 0.0,
                  jnp.exp(s - m_new[..., None]))
    corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - m_new))
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v_blk.astype(jnp.float32))
    return o_new, m_new, l_new


def _ring_attn_local(q, k, v, seg=None, *, axis_name, causal, scale):
    """Per-shard body (inside shard_map): q/k/v hold the local sequence
    chunk [B, Sq, H, D]; returns the local output chunk. seg: optional
    local packed-segment ids [B, Sq] — the k-side ids ride the SAME ring
    rotation as their k/v block."""
    n = jax.lax.psum(1, axis_name)  # static: axis size
    my = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    qpos = my * sq + jnp.arange(sq)
    qf = q.astype(jnp.float32) * scale
    perm = [(j, (j + 1) % n) for j in range(n)]

    def attend(o, m, l, k_blk, v_blk, kseg_blk, i):
        """Accumulate the block that originated at ring position
        (my - i) % n."""
        src = (my - i) % n
        kpos = (src * sq + jnp.arange(sq)) if causal else None
        return _online_block_update((o, m, l), qf, qpos, k_blk, v_blk, kpos,
                                    qseg=seg, kseg=kseg_blk)

    o0 = jnp.zeros((b, h, sq, d), jnp.float32)
    # step 0 visits the device's own (diagonal) block, which under a causal
    # mask has unmasked entries — so m turns finite before any fully masked
    # future block arrives and exp(-inf - finite) stays 0, not NaN.
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    o, m, l = attend(o0, m0, l0, k, v, seg, 0)
    if n > 1:
        # permute-at-top so the ring does n-1 rotations, not n (the block a
        # final rotation would produce is never attended).
        kseg0 = seg if seg is not None else jnp.zeros((b, sq), jnp.int32)

        def step(carry, i):
            o, m, l, k_blk, v_blk, kseg_blk = carry
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            kseg_blk = jax.lax.ppermute(kseg_blk, axis_name, perm)
            o, m, l = attend(o, m, l, k_blk, v_blk,
                             kseg_blk if seg is not None else None, i)
            return (o, m, l, k_blk, v_blk, kseg_blk), None

        (o, m, l, _, _, _), _ = jax.lax.scan(
            step, (o, m, l, k, v, kseg0), jnp.arange(1, n))
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)


def _ring_attn_zigzag(q, k, v, seg=None, *, axis_name, scale):
    """Causal ring attention over the ZIGZAG layout: the local sequence
    rows are half-chunks (j, 2n-1-j) of the 2n global half-chunks, so
    every device owns an equal mix of early and late positions. Each ring
    step considers 4 (q-half, k-half) pairs and computes a pair ONLY when
    its k-chunk index <= its q-chunk index (lax.cond on a per-device
    scalar — pure compute, no collectives inside the branch, so
    non-uniform branching across the ring is legal). Per-device work is
    exactly 2n+1 half-pairs for every rank — the balanced version of the
    contiguous ring where rank n-1 computes n full blocks while rank 0
    masks away all but one (the TODO this replaces); ~2x causal
    throughput at large n."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    hsq = sq // 2
    cl, ch = my, 2 * n - 1 - my              # local half-chunk indices
    qf = q.astype(jnp.float32) * scale
    q_halves = (qf[:, :hsq], qf[:, hsq:])
    q_chunks = (cl, ch)
    qpos = tuple(c * hsq + jnp.arange(hsq) for c in q_chunks)
    perm = [(j, (j + 1) % n) for j in range(n)]

    qseg_halves = (None, None)
    if seg is not None:
        qseg_halves = (seg[:, :hsq], seg[:, hsq:])

    def attend_pair(carry, k_half, v_half, kseg_half, qh_idx, kc):
        kpos = kc * hsq + jnp.arange(hsq)
        return _online_block_update(carry, q_halves[qh_idx], qpos[qh_idx],
                                    k_half, v_half, kpos,
                                    qseg=qseg_halves[qh_idx], kseg=kseg_half)

    def visit(carries, k_blk, v_blk, kseg_blk, src):
        """Process both k-halves of the block that originated at `src`
        against both local q-halves, skipping fully-masked pairs."""
        k_halves = (k_blk[:, :hsq], k_blk[:, hsq:])
        v_halves = (v_blk[:, :hsq], v_blk[:, hsq:])
        kseg_halves = ((kseg_blk[:, :hsq], kseg_blk[:, hsq:])
                       if seg is not None else (None, None))
        k_chunks = (src, 2 * n - 1 - src)
        new = []
        for qh in range(2):
            carry = carries[qh]
            for kh in range(2):
                kc = k_chunks[kh]
                carry = jax.lax.cond(
                    kc <= q_chunks[qh],
                    lambda c, kh=kh, qh=qh, kc=kc: attend_pair(
                        c, k_halves[kh], v_halves[kh], kseg_halves[kh],
                        qh, kc),
                    lambda c: c,
                    carry)
            new.append(carry)
        return tuple(new)

    def init_carry():
        return (jnp.zeros((b, h, hsq, d), jnp.float32),
                jnp.full((b, h, hsq), -jnp.inf, jnp.float32),
                jnp.zeros((b, h, hsq), jnp.float32))

    carries = (init_carry(), init_carry())
    carries = visit(carries, k, v, seg, my)  # own block first (diagonal)
    if n > 1:
        kseg0 = seg if seg is not None else jnp.zeros((b, sq), jnp.int32)

        def step(state, i):
            carries, k_blk, v_blk, kseg_blk = state
            k_blk = jax.lax.ppermute(k_blk, axis_name, perm)
            v_blk = jax.lax.ppermute(v_blk, axis_name, perm)
            kseg_blk = jax.lax.ppermute(kseg_blk, axis_name, perm)
            carries = visit(carries, k_blk, v_blk,
                            kseg_blk if seg is not None else None,
                            (my - i) % n)
            return (carries, k_blk, v_blk, kseg_blk), None

        (carries, _, _, _), _ = jax.lax.scan(
            step, (carries, k, v, kseg0), jnp.arange(1, n))

    outs = []
    for o, m, l in carries:
        outs.append(jnp.transpose(o / jnp.maximum(l, 1e-30)[..., None],
                                  (0, 2, 1, 3)))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def zigzag_sequence_perm(s, n):
    """Global permutation natural -> zigzag (device j holds half-chunks
    j and 2n-1-j); returns (perm, inverse). Public: models that permute
    the token stream ONCE (embedding output in, logits out) pay one
    gather each way per STEP instead of four per attention layer — pair
    with layout="zigzag_pre"."""
    import numpy as np

    hsq = s // (2 * n)
    order = []
    for j in range(n):
        order.extend(range(j * hsq, (j + 1) * hsq))
        order.extend(range((2 * n - 1 - j) * hsq, (2 * n - j) * hsq))
    perm = np.asarray(order)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(s)
    return perm, inv


def ring_attention_arrays(q, k, v, is_causal=True, scale=None, axis="sp",
                          layout="contiguous", segment_ids=None):
    """Array-level ring attention: [B,S,H,D] with S sharded over `axis`.

    layout="zigzag" (causal only) rebalances the ring: the sequence is
    permuted so each device holds an early+late half-chunk pair, every
    rank does identical work, and fully-masked pairs are skipped —
    ~2x causal throughput at large axis sizes for one gather each way.
    Falls back to the single-shard flash path when the axis is degenerate.

    segment_ids: optional [B, S] int32 packed-sequence ids (same layout
    as the token stream — for zigzag_pre that means ALREADY permuted);
    the k-side ids ride the ring rotation with their k/v blocks, so
    packed long-context batches keep context parallelism.
    """
    from ..ops.pallas_ops import flash_attention_arrays

    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    seg = None
    if segment_ids is not None:
        seg = jnp.asarray(segment_ids, jnp.int32)
    n = axis_size(axis)
    if n <= 1:
        return flash_attention_arrays(q, k, v, None, is_causal, scale,
                                      segment_ids=seg)
    if q.shape[1] % n != 0:
        warnings.warn(
            f"ring_attention: seq len {q.shape[1]} not divisible by {axis} axis "
            f"size {n}; falling back to full-sequence attention (peak memory "
            f"O(S^2) per chip instead of O((S/n)^2))."
        )
        return flash_attention_arrays(q, k, v, None, is_causal, scale,
                                      segment_ids=seg)

    mesh = get_mesh()
    # Only 'sp' is manual; batch/head dims stay in GSPMD-auto mode so dp/mp
    # sharding (and an enclosing pp pipeline) keep composing.
    spec = P(None, axis, None, None)
    seg_spec = P(None, axis)
    zig_ok = is_causal and q.shape[1] % (2 * n) == 0 and n > 1
    if layout in ("zigzag", "zigzag_pre") and not zig_ok:
        warnings.warn(
            "ring_attention: zigzag layout needs causal attention and seq "
            "divisible by 2*axis_size; using the contiguous ring instead.")
        layout = "contiguous"

    def mapped(body):
        if seg is None:
            fn = jax.shard_map(
                body, mesh=mesh, in_specs=(spec, spec, spec),
                out_specs=spec, axis_names=frozenset({axis}),
                check_vma=False)
            return lambda a, b_, c: fn(a, b_, c)
        fn = jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec, seg_spec),
            out_specs=spec, axis_names=frozenset({axis}), check_vma=False)
        return fn

    if layout == "zigzag_pre":
        # caller already permuted the sequence into zigzag order (one
        # model-level gather instead of per-layer ones); segment_ids
        # arrive in the same permuted order
        body = partial(_ring_attn_zigzag, axis_name=axis, scale=scale)
        fn = mapped(body)
        return fn(q, k, v, seg) if seg is not None else fn(q, k, v)
    if layout == "zigzag":
        perm, inv = zigzag_sequence_perm(q.shape[1], n)
        qz, kz, vz = (jnp.take(t, jnp.asarray(perm), axis=1)
                      for t in (q, k, v))
        segz = (jnp.take(seg, jnp.asarray(perm), axis=1)
                if seg is not None else None)
        body = partial(_ring_attn_zigzag, axis_name=axis, scale=scale)
        fn = mapped(body)
        out = fn(qz, kz, vz, segz) if seg is not None else fn(qz, kz, vz)
        return jnp.take(out, jnp.asarray(inv), axis=1)
    body = partial(_ring_attn_local, axis_name=axis, causal=is_causal, scale=scale)
    fn = mapped(body)
    return fn(q, k, v, seg) if seg is not None else fn(q, k, v)


def ring_attention(query, key, value, is_causal=True, scale=None, axis="sp",
                   layout="contiguous", name=None, segment_ids=None):
    """Tensor-level context-parallel attention (the long-context answer:
    seq stays sharded over 'sp' end to end — no all-gather of
    activations). layout="zigzag" load-balances the causal ring;
    segment_ids pack multiple documents per row (see
    ring_attention_arrays)."""
    seg_arr = None
    if segment_ids is not None:
        seg_arr = (segment_ids._data if hasattr(segment_ids, "_data")
                   else jnp.asarray(segment_ids))

    def fn(q, k, v):
        return ring_attention_arrays(q, k, v, is_causal, scale, axis,
                                     layout=layout, segment_ids=seg_arr)

    return apply(fn, query, key, value, name=name or "ring_attention")
