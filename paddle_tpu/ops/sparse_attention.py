"""Learned sparse attention (DeepSeek-V3.2's DSA, as Keye-VL-2.0's language
model states it in `sa_config`): a small INDEXER scores every earlier token
for each query, each query keeps the `topk` tokens scored highest, and GQA
attends those alone.

  I[t, s] = sum_j w[t, j] * relu(qI_j[t] . kI[s])     s <= t
  S_t     = the topk positions s <= t of largest I[t, s], ties to the
            lower position (`lax.top_k`'s rule); every s <= t while t < topk
  o_t     = softmax(q_t k[S_t]^T * scale) v[S_t]       query head h reads
                                                       K/V head h // G

`w` carries the indexer's scale factors (the model's business).  One index
key a token (`kI`, `index_dim` lanes) serves every index head; a cache keeps
it beside the token's K and V, in a third pool of the layer
(`serving.kv_cache.BlockKVCache(index_lanes=...)`) padded with zero lanes to
whole lane tiles (`index_pool_lanes`).

Selection is exact.  A score is mapped to an int32 that orders as the float
does (`sort_keys`; -0.0 is +0.0), the topk-th largest key `theta` of a row
is found bit by bit from the top (32 counting passes at most: a pass
decides one bit of `theta` by counting the keys at or above a candidate),
and where more keys than topk tie at `theta` the position `last` of the
tie that is the topk-th is found the same way over positions.  A position
is selected iff it is valid and `key > theta`, or `key == theta` and
`pos <= last` (`selected`).  So a row's selection is exactly
`lax.top_k(I[t, :t + 1], topk)`'s positions.

Two entry points, each with a Pallas kernel path (TPU, or the CPU under
PTPU_PALLAS_INTERPRET=1) and an XLA path of the same arithmetic, counted
under PTPU_ATTN_DEBUG=1 as `dsa_prefill_kernel` / `dsa_decode_kernel` and
`dsa_fallback:<why>`:

- `sparse_prefill_attention` (a whole prompt, attending its own keys):
  query rows in super-blocks of `_SUPER_ROWS`; for each, `dsa_prefill_scores`
  writes the rows' scores against every key up to the diagonal, tile by
  tile, to HBM (`[rows, T]` float32, nothing past the diagonal is
  computed or written), `dsa_select` finds each row's (theta, last), and
  `dsa_prefill_attention` runs causal flash attention over K/V STREAMED by
  tiles, each tile's scores read back and turned into the mask.  The
  attention does the dense FLOPs under a mask (a token-level selection
  leaves nearly every tile with a selected key); the mask is never
  recomputed, so it is the selection exactly.  Its products go by K/V
  head: a head's G query heads are the rows of ONE operand `[G*tq, d]`,
  so each K and V tile feeds the MXU once a K/V head, and the mask is an
  additive bias made once a tile and shared by the G members.
- `sparse_decode_attention` (rows against a paged pool, one query a row):
  `dsa_decode_scores` streams ONLY a row's live blocks of the index pool
  through its block table (a tile of `_DECODE_TILE` tokens a step, a ring
  of DMAs ahead of the products) and writes its scores, `dsa_select`
  selects, and `dsa_decode_attention` streams the row's K and V the same
  way with every key outside the selection masked: a DMA cannot take one
  token's row out of a pool tiled (16, 128), so the kernel attends topk
  keys but reads the whole context.  The XLA form compacts the selection
  to positions and gathers those tokens' K and V rows by slot.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .pallas_ops import _count_path, _dot_f32, _interpret, _on_tpu
from .ragged_paged_attention import _ring_dma_loop

__all__ = ["index_pool_lanes", "index_scores", "sort_keys", "selected",
           "select_threshold", "sparse_prefill_attention",
           "sparse_decode_attention"]

_INT_MIN = -2 ** 31
_NEG = -1e30                  # a masked score in the flash state
_SUPER_ROWS = 2048            # query rows a prefill's scores are held for
_PREFILL_TQ = 256             # query rows of a score tile
_FLASH_TQ = 128               # query rows of a flash tile
_TK = 1024                    # keys of a prefill tile (scores and flash)
_DECODE_TILE = 1024           # index keys a decode step's tile holds
_DECODE_RING = 3              # tiles of the decode stream held at once
_SELECT_ROWS = 8              # rows a selection program holds
_SELECT_CHUNK = 2048          # lanes a counting pass takes at a time
_VMEM_BYTES = 48 * 2 ** 20


def index_pool_lanes(dim: int) -> int:
    """Lanes an index key takes as pooled and as the kernels read it."""
    return -(-int(dim) // 128) * 128


def _kernel_wanted(kind: str) -> bool:
    if not (_on_tpu() or _interpret()):
        _count_path("dsa_fallback:off_tpu")
        return False
    _count_path(f"dsa_{kind}_kernel")
    return True


# ---------------------------------------------------------------------------
# the arithmetic both paths share
# ---------------------------------------------------------------------------

def index_scores(qi, w, ki):
    """qi [..., Hi, D], w [..., Hi] float32, ki [..., L, D] (broadcast
    against qi's leading dims) -> I [..., L] float32, the heads summed in
    order."""
    acc = None
    for j in range(qi.shape[-2]):
        s = jnp.einsum("...d,...ld->...l", qi[..., j, :], ki,
                       preferred_element_type=jnp.float32)
        term = w[..., j:j + 1] * jnp.maximum(s, 0.0)
        acc = term if acc is None else acc + term
    return acc


def sort_keys(s):
    """float32 scores -> int32 keys in the same order (-0.0 as +0.0)."""
    s = jnp.where(s == 0.0, 0.0, s)
    b = jax.lax.bitcast_convert_type(s, jnp.int32)
    return jnp.where(b < 0, b ^ 0x7FFFFFFF, b)


def selected(keys, pos, valid, theta, last):
    """The selection rule: valid & (key > theta | key == theta & pos <=
    last); theta, last broadcast against keys."""
    return valid & ((keys > theta) | ((keys == theta) & (pos <= last)))


def _pos_bits(length: int) -> int:
    return max(length, 1).bit_length()


def select_threshold(keys, lens, topk):
    """XLA form: keys [R, L] int32, lens [R] (positions < lens[r] valid)
    -> (theta [R, 1], last [R, 1]) int32, the module docstring's rule."""
    r, n = keys.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (r, n), 1)
    keys = jnp.where(pos < lens[:, None], keys, _INT_MIN)

    def count(mask):
        return jnp.sum(mask.astype(jnp.int32), axis=1, keepdims=True)

    def bit(i, ans):       # ans: the biased (unsigned) bits decided so far
        cand = ans | jax.lax.shift_right_logical(jnp.int32(_INT_MIN), i)
        return jnp.where(count(keys >= (cand ^ _INT_MIN)) >= topk, cand,
                         ans)

    ans = jax.lax.fori_loop(0, 32, bit, jnp.zeros((r, 1), jnp.int32))
    theta = ans ^ _INT_MIN
    need = topk - count(keys > theta)
    tie = keys == theta
    nbits = _pos_bits(n)

    def place(i, last):
        cand = last | jax.lax.shift_left(jnp.int32(1), nbits - 1 - i)
        return jnp.where(count(tie & (pos < cand)) < need, cand, last)

    last = jax.lax.fori_loop(0, nbits, place, jnp.zeros((r, 1), jnp.int32))
    return theta, last


# ---------------------------------------------------------------------------
# dsa_select: (theta, last) of rows whose scores are in HBM
# ---------------------------------------------------------------------------

def _select_kernel(lens_s, len_ref, s_ref, o_ref, keys_ref, flag_ref, *,
                   topk, nbits, chunk):
    """One program a block of `_SELECT_ROWS` rows, every score of them in
    VMEM as keys.  Counting passes run over the lanes up to the block's
    longest row, `chunk` at a time.  The bits of theta are decided
    from the top and the loop stops once every row holds exactly `topk`
    keys at or above what is decided (theta is then the least of them);
    the position passes run only where a row's ties overflow.  A vector's
    verdict reaches the scalar loop through `flag_ref` (SMEM)."""
    from jax.experimental import pallas as pl

    rows, width = s_ref.shape
    first = pl.program_id(0) * rows
    longest = lens_s[first]
    for i in range(1, rows):
        longest = jnp.maximum(longest, lens_s[first + i])
    lens = len_ref[:, 0:1]                                   # [R, 1]
    n_ch = jnp.clip((longest + chunk - 1) // chunk, 1,
                    width // chunk)

    def fill(c, carry):
        lo = pl.multiple_of(c * chunk, chunk)
        pos = lo + jax.lax.broadcasted_iota(jnp.int32,
                                            (rows, chunk), 1)
        keys_ref[:, pl.ds(lo, chunk)] = jnp.where(
            pos < lens, sort_keys(s_ref[:, pl.ds(lo, chunk)]),
            _INT_MIN)
        return carry

    jax.lax.fori_loop(0, n_ch, fill, 0)

    def lanes_of(x):
        """x [R, chunk] -> its 128-lane slices (vregs; no data moves)."""
        return [x[:, u * 128:(u + 1) * 128] for u in range(chunk // 128)]

    def count(pred):
        """sum over the block's lanes of pred(keys chunk, pos chunk): the
        chunks' lane tiles added elementwise, ONE cross-lane sum a pass."""
        def body(c, acc):
            lo = pl.multiple_of(c * chunk, chunk)
            pos = lo + jax.lax.broadcasted_iota(
                jnp.int32, (rows, chunk), 1)
            hit = jnp.where(pred(keys_ref[:, pl.ds(lo, chunk)], pos), 1.0,
                            0.0)
            for part in lanes_of(hit):
                acc = acc + part
            return acc
        return jnp.sum(jax.lax.fori_loop(
            0, n_ch, body, jnp.zeros((rows, 128), jnp.float32)), axis=1,
            keepdims=True)

    def bits_left(state):
        return (state[0] < 32) & (state[3] > 0)

    def decide(state):
        i, ans, n_at, _ = state
        cand = ans | jax.lax.shift_right_logical(jnp.int32(_INT_MIN), i)
        n_ge = count(lambda k, p: k >= (cand ^ _INT_MIN))
        ok = n_ge >= topk
        ans, n_at = jnp.where(ok, cand, ans), jnp.where(ok, n_ge, n_at)
        flag_ref[0] = jnp.sum(jnp.where(n_at == topk, 0, 1))
        return i + 1, ans, n_at, flag_ref[0]

    # every lane scanned is at or above INT_MIN, the bound decided so far
    scanned = jnp.full((rows, 1), 1.0, jnp.float32) * (
        n_ch * chunk).astype(jnp.float32)
    flag_ref[0] = jnp.sum(jnp.where(scanned == topk, 0, 1))
    _, ans, _, _ = jax.lax.while_loop(
        bits_left, decide, (jnp.int32(0), jnp.zeros((rows, 1), jnp.int32),
                            scanned, flag_ref[0]))
    lower = ans ^ _INT_MIN                 # theta's lower bound so far

    def least(c, acc):
        lo = pl.multiple_of(c * chunk, chunk)
        k = keys_ref[:, pl.ds(lo, chunk)]
        s = jnp.where(k >= lower, s_ref[:, pl.ds(lo, chunk)], jnp.inf)
        for part in lanes_of(s):
            acc = jnp.minimum(acc, part)
        return acc

    # the least score whose key is at or above the bound: theta itself
    # (with fewer valid keys than topk the bound is INT_MIN, and so is
    # theta); the minimum is taken over the floats, which order alike
    theta = sort_keys(jnp.min(jax.lax.fori_loop(
        0, n_ch, least, jnp.full((rows, 128), jnp.inf, jnp.float32)),
        axis=1, keepdims=True))
    theta = jnp.where(lower == _INT_MIN, _INT_MIN, theta)
    need = topk - count(lambda k, p: k > theta)
    n_tie = count(lambda k, p: k == theta)

    def place(i, last):
        cand = last | jax.lax.shift_left(jnp.int32(1), nbits - 1 - i)
        before = count(lambda k, p: (k == theta) & (p < cand))
        return jnp.where(before < need, cand, last)

    last0 = jnp.full((rows, 1), 2 ** nbits - 1, jnp.int32)
    lane = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)

    def write(last):
        o_ref[...] = jnp.where(lane == 0, theta,
                               jnp.where(lane == 1, last, 0))

    write(last0)
    flag_ref[0] = jnp.sum(jnp.where(n_tie > need, 1, 0))

    @pl.when(flag_ref[0] > 0)
    def _ties():
        write(jnp.where(n_tie > need, jax.lax.fori_loop(
            0, nbits, place, jnp.zeros((rows, 1), jnp.int32)), last0))


def _select_call(scores, lens, topk, interpret):
    """scores [R, L] float32 (R a multiple of _SELECT_ROWS, L of 128),
    lens [R] -> [R, 128] int32: theta in lane 0, last in
    lane 1."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, n = scores.shape
    lens = jnp.asarray(lens, jnp.int32).reshape(r)
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk,
                          nbits=_pos_bits(n),
                          chunk=math.gcd(n, _SELECT_CHUNK)),
        name="dsa_select",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r // _SELECT_ROWS,),
            in_specs=[pl.BlockSpec((_SELECT_ROWS, 128),
                                   lambda i, lens: (i, 0)),
                      pl.BlockSpec((_SELECT_ROWS, n),
                                   lambda i, lens: (i, 0))],
            out_specs=pl.BlockSpec((_SELECT_ROWS, 128),
                                   lambda i, lens: (i, 0)),
            scratch_shapes=[pltpu.VMEM((_SELECT_ROWS, n), jnp.int32),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((r, 128), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(lens, jnp.broadcast_to(lens[:, None], (r, 128)), scores)


# ---------------------------------------------------------------------------
# prefill: scores to HBM by tiles, selection, flash over streamed K/V
# ---------------------------------------------------------------------------

def _diag(row0, i, tq, tk):
    """The last key tile query tile i of a super-block reaches."""
    return (row0 + (i + 1) * tq - 1) // tk


def _prefill_scores_kernel(row0_ref, q_ref, w_ref, k_ref, o_ref, *, hi):
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)
    tq, tk = o_ref.shape
    first = row0_ref[0] + i * tq

    @pl.when(j <= _diag(row0_ref[0], i, tq, tk))
    def _tile():
        kt = k_ref[...]
        w = w_ref[...]
        acc = None
        for h in range(hi):
            s = _dot_f32(q_ref[h], kt, transpose_b=True)       # [tq, tk]
            term = w[:, h:h + 1] * jnp.maximum(s, 0.0)
            acc = term if acc is None else acc + term
        qpos = first + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        o_ref[...] = jnp.where(kpos <= qpos, acc, -jnp.inf)


def _prefill_scores_call(row0, qi_t, w, ki, interpret):
    """qi_t [Hi, R, 128], w [R, 128] float32, ki [T, 128] -> [R, T]
    float32 scores of rows row0 .. row0 + R - 1; entries past a row's
    diagonal tile are never written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hi, r, lanes = qi_t.shape
    t = ki.shape[0]
    tq, tk = _PREFILL_TQ, _TK

    def kmap(i, j, row0):
        return jnp.minimum(j, _diag(row0[0], i, tq, tk)), 0

    def omap(i, j, row0):
        return i, jnp.minimum(j, _diag(row0[0], i, tq, tk))

    return pl.pallas_call(
        functools.partial(_prefill_scores_kernel, hi=hi),
        name="dsa_prefill_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r // tq, t // tk),
            in_specs=[pl.BlockSpec((hi, tq, lanes),
                                   lambda i, j, row0: (0, i, 0)),
                      pl.BlockSpec((tq, 128), lambda i, j, row0: (i, 0)),
                      pl.BlockSpec((tk, lanes), kmap)],
            out_specs=pl.BlockSpec((tq, tk), omap)),
        out_shape=jax.ShapeDtypeStruct((r, t), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(jnp.reshape(row0, (1,)).astype(jnp.int32), qi_t, w, ki)


def _prefill_flash_kernel(row0_ref, q_ref, k_ref, v_ref, s_ref, sel_ref,
                          o_ref, qg_ref, m_ref, l_ref, acc_ref, *, d, scale):
    """One program a (query tile i, key tile j).  A K/V head's G query
    heads are stacked as the rows of one operand, `qg_ref[g]` [G*tq, d]
    (head g*G + u at rows u*tq..), scaled once when the query tile
    arrives, so each K and V tile feeds the MXU once a K/V head.  The
    selection is applied once a tile, as an additive bias (0 or _NEG)
    broadcast over the G members: a row whose tiles so far hold no
    selected key sums junk under m = _NEG, which alpha = exp(_NEG - m)
    = 0 clears when its first selected key arrives."""
    from jax.experimental import pallas as pl

    i, j = pl.program_id(0), pl.program_id(1)
    tq, tk = s_ref.shape
    kv_heads, rows, _ = qg_ref.shape
    group = rows // tq
    diag = _diag(row0_ref[0], i, tq, tk)

    @pl.when(j == 0)
    def _init():
        for h in range(kv_heads * group):
            g, u = divmod(h, group)
            qg_ref[g, u * tq:(u + 1) * tq, :] = (
                q_ref[:, h * d:(h + 1) * d].astype(jnp.float32) * scale
            ).astype(qg_ref.dtype)
        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j <= diag)
    def _tile():
        qpos = row0_ref[0] + i * tq + jax.lax.broadcasted_iota(
            jnp.int32, (tq, tk), 0)
        kpos = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        chosen = selected(sort_keys(s_ref[...]), kpos, kpos <= qpos,
                          sel_ref[:, 0:1], sel_ref[:, 1:2])
        bias = jnp.where(chosen, 0.0, _NEG)                    # [tq, tk]
        for g in range(kv_heads):
            sc = _dot_f32(qg_ref[g], k_ref[:, g * d:(g + 1) * d],
                          transpose_b=True)                    # [G*tq, tk]
            sc = (sc.reshape(group, tq, tk) + bias).reshape(rows, tk)
            m_prev = m_ref[g]                                  # [G*tq, 128]
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
            p = jnp.exp(sc - m_new[:, 0:1])
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = alpha * l_ref[g] + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha[:, 0:1] + _dot_f32(
                p.astype(v_ref.dtype), v_ref[:, g * d:(g + 1) * d])
            m_ref[g] = m_new

    @pl.when(j == diag)
    def _out():
        for h in range(kv_heads * group):
            g, u = divmod(h, group)
            at = slice(u * tq, (u + 1) * tq)
            o_ref[:, h * d:(h + 1) * d] = (
                acc_ref[g, at] / jnp.maximum(l_ref[g, at][:, 0:1], 1e-30)
            ).astype(o_ref.dtype)


def _prefill_flash_call(row0, q, k, v, scores, sel, heads, kv_heads, d,
                        scale, interpret):
    """q [R, H*d] rows row0.., k, v [T, Hkv*d], scores [R, T], sel [R, 128]
    -> o [R, H*d]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = q.shape[0]
    t = k.shape[0]
    tq, tk = _FLASH_TQ, _TK
    rows = heads // kv_heads * tq

    def kmap(i, j, row0):
        return jnp.minimum(j, _diag(row0[0], i, tq, tk)), 0

    def smap(i, j, row0):
        return i, jnp.minimum(j, _diag(row0[0], i, tq, tk))

    def rowmap(i, j, row0):
        return i, 0

    return pl.pallas_call(
        functools.partial(_prefill_flash_kernel, d=d, scale=scale),
        name="dsa_prefill_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(r // tq, t // tk),
            in_specs=[pl.BlockSpec((tq, heads * d), rowmap),
                      pl.BlockSpec((tk, kv_heads * d), kmap),
                      pl.BlockSpec((tk, kv_heads * d), kmap),
                      pl.BlockSpec((tq, tk), smap),
                      pl.BlockSpec((tq, 128), rowmap)],
            out_specs=pl.BlockSpec((tq, heads * d), rowmap),
            scratch_shapes=[pltpu.VMEM((kv_heads, rows, d), q.dtype),
                            pltpu.VMEM((kv_heads, rows, 128), jnp.float32),
                            pltpu.VMEM((kv_heads, rows, 128), jnp.float32),
                            pltpu.VMEM((kv_heads, rows, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((r, heads * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(jnp.reshape(row0, (1,)).astype(jnp.int32), q, k, v, scores, sel)


def _prefill_kernel_path(q, k, v, qi, ki, w, topk, scale):
    """One sequence: q [T, H, d], k, v [T, Hkv, d], qi [T, Hi, Di], ki [T,
    Di], w [T, Hi] -> o [T, H, d], by super-blocks of query rows."""
    t, heads, d = q.shape
    kv_heads = k.shape[1]
    hi, di = qi.shape[1], qi.shape[2]
    lanes = index_pool_lanes(di)
    tp = -(-t // _TK) * _TK
    rows = _SUPER_ROWS if tp % _SUPER_ROWS == 0 else _TK
    interpret = _interpret()

    def rows_of(x):
        return jnp.pad(x, ((0, tp - t),) + ((0, 0),) * (x.ndim - 1))

    qf = rows_of(q.reshape(t, heads * d))
    kf = rows_of(k.reshape(t, kv_heads * d))
    vf = rows_of(v.reshape(t, kv_heads * d))
    qi_t = jnp.swapaxes(rows_of(jnp.pad(
        qi, ((0, 0), (0, 0), (0, lanes - di)))), 0, 1)       # [Hi, Tp, 128]
    ki_p = rows_of(jnp.pad(ki, ((0, 0), (0, lanes - di)))).astype(qi.dtype)
    w_p = rows_of(jnp.pad(w.astype(jnp.float32), ((0, 0), (0, 128 - hi))))

    def block(sb, out):
        row0 = sb * rows
        with jax.named_scope("dsa/indexer"):
            scores = _prefill_scores_call(
                row0, jax.lax.dynamic_slice_in_dim(qi_t, row0, rows, 1),
                jax.lax.dynamic_slice_in_dim(w_p, row0, rows), ki_p,
                interpret)
        with jax.named_scope("dsa/select"):
            lens = row0 + 1 + jnp.arange(rows, dtype=jnp.int32)
            sel = _select_call(scores, lens, topk, interpret)
        with jax.named_scope("attn/sparse"):
            o = _prefill_flash_call(
                row0, jax.lax.dynamic_slice_in_dim(qf, row0, rows), kf, vf,
                scores, sel, heads, kv_heads, d, scale, interpret)
        return jax.lax.dynamic_update_slice_in_dim(out, o, row0, 0)

    out = jax.lax.fori_loop(0, tp // rows, block, jnp.zeros_like(qf))
    return out[:t].reshape(t, heads, d)


def _prefill_xla(q, k, v, qi, ki, w, topk, scale):
    """The same arithmetic over whole [T, T] arrays (small T)."""
    t, heads, d = q.shape
    group = heads // k.shape[1]
    with jax.named_scope("dsa/indexer"):
        scores = index_scores(qi, w.astype(jnp.float32), ki[None])   # [T, T]
    with jax.named_scope("dsa/select"):
        pos = jnp.arange(t, dtype=jnp.int32)
        valid = pos[None, :] <= pos[:, None]
        keys = sort_keys(jnp.where(valid, scores, -jnp.inf))
        theta, last = select_threshold(keys, pos + 1, topk)
        chosen = selected(keys, pos[None, :], valid, theta, last)
    with jax.named_scope("attn/sparse"):
        kk = jnp.repeat(k, group, axis=1)
        vv = jnp.repeat(v, group, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q, kk,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(chosen[None], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        p = jnp.where(chosen[None], p, 0.0)
        return jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), vv,
                          preferred_element_type=jnp.float32).astype(q.dtype)


def sparse_prefill_attention(q, k, v, qi, ki, w, topk, scale):
    """A whole prompt's sparse attention over its own keys.

    q [B, T, H, d], k, v [B, T, Hkv, d], qi [B, T, Hi, Di] index queries,
    ki [B, T, Di] index keys, w [B, T, Hi] index weights -> o [B, T, H, d].
    Rows of the batch are sequences of their own."""
    use_kernel = _kernel_wanted("prefill")
    fn = _prefill_kernel_path if use_kernel else _prefill_xla
    return jnp.stack([fn(q[b], k[b], v[b], qi[b], ki[b], w[b], topk, scale)
                      for b in range(q.shape[0])])


# ---------------------------------------------------------------------------
# decode: a row's scores against its live index blocks, selection, gather
# ---------------------------------------------------------------------------

def _decode_scores_kernel(len_ref, tbl_ref, q_ref, w_ref, pool_hbm, o_ref,
                          buf, sem, *, bs, nb, maxb):
    """One program a row r: its index keys stream from HBM through its
    block table, a TILE of `buf.shape[2] // bs` blocks gathered by as many
    DMAs into one buffer (a ring of `buf.shape[0]`, copies in flight under
    the products of the tiles before), each tile scored by ONE product
    `qI [Hi, lanes] x keys^T`, then relu, the weights and the sum over
    heads; positions past the row's length read -inf."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = pl.program_id(0)
    length = len_ref[r]
    q = q_ref[0]                                          # [Hi, lanes]
    wcol = w_ref[0][:, 0:1]                               # [Hi, 1]
    t_rows = buf.shape[2]
    tile = t_rows // bs
    num_kb = jnp.minimum((length + bs - 1) // bs, maxb)
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, jnp.float32)

    def copies(slot, t):
        dmas = []
        for i in range(tile):
            kb = jnp.minimum(t * tile + i, num_kb - 1)
            blk = jnp.clip(tbl_ref[r, kb], 0, nb - 1)
            dmas.append(pltpu.make_async_copy(
                pool_hbm.at[pl.ds(blk, 1)],
                buf.at[slot, :, pl.ds(i * bs, bs)], sem.at[slot]))
        return dmas

    def step(slot, t, carry):
        for c in copies(slot, t):
            c.wait()
        s = _dot_f32(q, buf[slot, 0], transpose_b=True)   # [Hi, T]
        sc = jnp.sum(jnp.maximum(s, 0.0) * wcol, axis=0, keepdims=True)
        pos = t * t_rows + jax.lax.broadcasted_iota(jnp.int32,
                                                    (1, t_rows), 1)
        lo = pl.multiple_of(t * t_rows, t_rows)
        o_ref[0, :, pl.ds(lo, t_rows)] = jnp.where(pos < length, sc,
                                                   -jnp.inf)
        return carry

    n_tiles = (num_kb + tile - 1) // tile
    _ring_dma_loop(n_tiles, copies, step, 0, buf.shape[0])


def _decode_scores_call(qi, w, pool, tables, lens, interpret):
    """qi [R, Hi, Di], w [R, Hi], pool [nb, bs, lanes], tables [R, maxb],
    lens [R] -> [R, maxb * bs] float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, hi, di = qi.shape
    nb, bs, lanes = pool.shape
    maxb = int(tables.shape[1])
    t_rows = max(1, _DECODE_TILE // bs) * bs
    width = -(-maxb * bs // t_rows) * t_rows
    hp = -(-hi // 8) * 8
    q = jnp.pad(qi, ((0, 0), (0, hp - hi), (0, lanes - di))).astype(
        pool.dtype)
    wcol = jnp.broadcast_to(jnp.pad(w.astype(jnp.float32),
                                    ((0, 0), (0, hp - hi)))[..., None],
                            (r, hp, 128))
    out = pl.pallas_call(
        functools.partial(_decode_scores_kernel, bs=bs, nb=nb, maxb=maxb),
        name="dsa_decode_scores",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(r,),
            in_specs=[pl.BlockSpec((1, hp, lanes), lambda i, *pre: (i, 0, 0)),
                      pl.BlockSpec((1, hp, 128), lambda i, *pre: (i, 0, 0)),
                      pl.BlockSpec(memory_space=pltpu.HBM)],
            out_specs=pl.BlockSpec((1, 1, width), lambda i, *pre: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_DECODE_RING, 1, t_rows, lanes), pool.dtype),
                pltpu.SemaphoreType.DMA((_DECODE_RING,))]),
        out_shape=jax.ShapeDtypeStruct((r, 1, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(lens, jnp.int32).reshape(r),
      jnp.asarray(tables, jnp.int32), q, wcol, pool)
    return out[:, 0, :maxb * bs]


def _decode_scores_xla(qi, w, pool, tables, lens):
    nb, bs, lanes = pool.shape
    r, maxb = tables.shape
    di = qi.shape[-1]
    keys = jnp.take(pool, jnp.clip(tables, 0, nb - 1), axis=0)
    keys = keys.reshape(r, maxb * bs, lanes)[..., :di]
    scores = index_scores(qi, w.astype(jnp.float32), keys)       # [R, L]
    pos = jnp.arange(maxb * bs, dtype=jnp.int32)[None]
    return jnp.where(pos < lens[:, None], scores, -jnp.inf)


_ATTN_TILE = 512              # tokens of K and V a decode attention step
_ATTN_RING = 3


def _decode_attn_kernel(len_ref, tbl_ref, q_ref, sc_ref, sel_ref, k_hbm,
                        v_hbm, o_ref, kbuf, vbuf, sem, *, bs, nb, maxb,
                        kv_heads, d, scale):
    """One program a row r: its K and V stream from HBM through its block
    table a tile of `kbuf.shape[2] // bs` blocks at a time (a ring of
    DMAs ahead of the products, as the index stream), and each tile's keys
    are masked to the row's selection - its scores' keys against (theta,
    last), `selected` - before each K/V head's two MXU products, the
    head's query heads as their rows, online softmax in float32.  The
    selection is applied, not gathered: a DMA cannot take one token's row
    out of a pool tiled (16, 128) on its last two dimensions, so the
    stream reads every live block (the keys a row attends are its topk;
    the bytes it moves are its whole context's)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r = pl.program_id(0)
    length = len_ref[r]
    q = q_ref[0]                                          # [H, d]
    group = q.shape[0] // kv_heads
    theta, last = sel_ref[0][:, 0:1], sel_ref[0][:, 1:2]  # [1, 1]
    t_rows = kbuf.shape[2]
    tile = t_rows // bs
    num_kb = jnp.minimum((length + bs - 1) // bs, maxb)

    def copies(slot, t):
        dmas = []
        for i in range(tile):
            kb = jnp.minimum(t * tile + i, num_kb - 1)
            blk = jnp.clip(tbl_ref[r, kb], 0, nb - 1)
            for src, buf, j in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                dmas.append(pltpu.make_async_copy(
                    src.at[pl.ds(blk, 1)],
                    buf.at[slot, :, pl.ds(i * bs, bs)], sem.at[j, slot]))
        return dmas

    def step(slot, t, carry):
        for c in copies(slot, t):
            c.wait()
        lo = pl.multiple_of(t * t_rows, t_rows)
        pos = lo + jax.lax.broadcasted_iota(jnp.int32, (1, t_rows), 1)
        chosen = selected(sort_keys(sc_ref[0, :, pl.ds(lo, t_rows)]), pos,
                          pos < length, theta, last)          # [1, T]
        keys, vals = kbuf[slot, 0], vbuf[slot, 0]             # [T, Hkv*d]
        out = []
        for g in range(kv_heads):
            m, l, acc = carry[g]
            s = _dot_f32(q[g * group:(g + 1) * group],
                         keys[:, g * d:(g + 1) * d],
                         transpose_b=True) * scale            # [G, T]
            s = jnp.where(chosen, s, _NEG)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(chosen, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            out.append((m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                        alpha * acc + _dot_f32(p.astype(vals.dtype),
                                               vals[:, g * d:(g + 1) * d])))
        return tuple(out)

    state0 = tuple((jnp.full((group, 1), _NEG, jnp.float32),
                    jnp.zeros((group, 1), jnp.float32),
                    jnp.zeros((group, d), jnp.float32))
                   for _ in range(kv_heads))
    n_tiles = (num_kb + tile - 1) // tile
    final = _ring_dma_loop(n_tiles, copies, step, state0, kbuf.shape[0])
    o_ref[0] = jnp.concatenate(
        [acc / jnp.maximum(l, 1e-30) for _, l, acc in final],
        axis=0).astype(o_ref.dtype)


def _decode_attn_call(q, scores, sel, k_pool, v_pool, tables, lens, scale,
                      interpret):
    """q [R, H, d], scores [R, W] float32 (W a multiple of _ATTN_TILE),
    sel [R, 128] (`_select_call`'s), pools [nb, bs, Hkv*d] -> [R, H, d]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    r, heads, d = q.shape
    nb, bs, hd = k_pool.shape
    maxb = int(tables.shape[1])
    width = scores.shape[1]
    t_rows = max(1, _ATTN_TILE // bs) * bs
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    return pl.pallas_call(
        functools.partial(_decode_attn_kernel, bs=bs, nb=nb, maxb=maxb,
                          kv_heads=hd // d, d=d, scale=scale),
        name="dsa_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(r,),
            in_specs=[pl.BlockSpec((1, heads, d), lambda i, *pre: (i, 0, 0)),
                      pl.BlockSpec((1, 1, width), lambda i, *pre: (i, 0, 0)),
                      pl.BlockSpec((1, 1, 128), lambda i, *pre: (i, 0, 0)),
                      hbm, hbm],
            out_specs=pl.BlockSpec((1, heads, d), lambda i, *pre: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_ATTN_RING, 1, t_rows, hd), k_pool.dtype),
                pltpu.VMEM((_ATTN_RING, 1, t_rows, hd), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, _ATTN_RING))]),
        out_shape=jax.ShapeDtypeStruct((r, heads, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.asarray(lens, jnp.int32).reshape(r), jnp.asarray(tables, jnp.int32),
      q, scores[:, None], sel[:, None], k_pool, v_pool)


def _compact(chosen, topk):
    """chosen [R, L] bool -> (positions [R, topk] of the first topk chosen
    in order, how many [R])."""
    csum = jnp.cumsum(chosen.astype(jnp.int32), axis=1)
    want = jnp.arange(1, topk + 1, dtype=jnp.int32)
    idx = jax.vmap(lambda c: jnp.searchsorted(c, want, side="left"))(csum)
    return idx.astype(jnp.int32), csum[:, -1]


def sparse_decode_attention(q, qi, w, k_pool, v_pool, i_pool, tables, lens,
                            topk, scale):
    """One query a row against its paged context (the row's own token
    already written).

    q [R, H, d], qi [R, Hi, Di], w [R, Hi], k_pool, v_pool [nb, bs,
    Hkv*d], i_pool [nb, bs, lanes], tables [R, maxb], lens [R] (0: a
    padding row) -> o [R, H, d]."""
    lens = jnp.asarray(lens, jnp.int32)
    tables = jnp.asarray(tables, jnp.int32)
    fn = _decode_kernels if _kernel_wanted("decode") else _decode_xla
    return fn(q, qi, w, k_pool, v_pool, i_pool, tables, lens, topk, scale)


def _decode_kernels(q, qi, w, k_pool, v_pool, i_pool, tables, lens, topk,
                    scale):
    """`sparse_decode_attention` through the three Mosaic calls."""
    r = q.shape[0]
    interpret = _interpret()
    with jax.named_scope("dsa/indexer"):
        scores = _decode_scores_call(qi, w, i_pool, tables, lens,
                                     interpret)
    with jax.named_scope("dsa/select"):
        rp = -(-r // _SELECT_ROWS) * _SELECT_ROWS
        wp = -(-scores.shape[1] // _SELECT_CHUNK) * _SELECT_CHUNK
        scores = jnp.pad(scores, ((0, rp - r), (0, wp - scores.shape[1])),
                         constant_values=-jnp.inf)
        sel = _select_call(scores, jnp.pad(lens, (0, rp - r)), topk,
                           interpret)
    with jax.named_scope("attn/sparse"):
        return _decode_attn_call(q, scores[:r], sel[:r], k_pool, v_pool,
                                 tables, lens, scale, interpret)


def _decode_xla(q, qi, w, k_pool, v_pool, i_pool, tables, lens, topk, scale):
    """`sparse_decode_attention`'s XLA form: the index pool gathered whole,
    the selection compacted to positions, the selected tokens' K and V
    gathered by slot."""
    r, heads, d = q.shape
    nb, bs, hd = k_pool.shape
    kv_heads = hd // d
    group = heads // kv_heads
    with jax.named_scope("dsa/indexer"):
        scores = _decode_scores_xla(qi, w, i_pool, tables, lens)
    with jax.named_scope("dsa/select"):
        width = scores.shape[1]
        keys = sort_keys(scores)
        theta, last = select_threshold(keys, lens, topk)
        pos = jnp.arange(width, dtype=jnp.int32)[None]
        chosen = selected(keys, pos, pos < lens[:, None], theta, last)
        idx, n_sel = _compact(chosen, topk)
    with jax.named_scope("attn/sparse"):
        at = jnp.minimum(idx, width - 1)
        blk = jnp.take_along_axis(tables, at // bs, axis=1)
        slot = jnp.clip(blk, 0, nb - 1) * bs + at % bs            # [R, topk]
        kg = jnp.take(k_pool.reshape(nb * bs, hd), slot, axis=0).reshape(
            r, topk, kv_heads, d)
        vg = jnp.take(v_pool.reshape(nb * bs, hd), slot, axis=0).reshape(
            r, topk, kv_heads, d)
        qg = q.reshape(r, kv_heads, group, d)
        s = jnp.einsum("rgqd,rkgd->rgqk", qg, kg,
                       preferred_element_type=jnp.float32) * scale
        live = (jnp.arange(topk)[None] < n_sel[:, None])[:, None, None]
        s = jnp.where(live, s, _NEG)
        p = jnp.where(live, jax.nn.softmax(s, axis=-1), 0.0)
        o = jnp.einsum("rgqk,rkgd->rgqd", p.astype(v_pool.dtype), vg,
                       preferred_element_type=jnp.float32)
        return o.reshape(r, heads, d).astype(q.dtype)
