"""Power retention (degree 2): attention whose weight of key s for query t
is `exp(b_t - b_s) * (q_t . k_s)^2` over the sum of those weights, `b` the
running sum of a per-head log gate (Brumby's layers, models/brumby.py;
"Scaling Context Requires Rethinking Attention", arXiv:2507.04239).
Because `(q . k)^2 = phi(q) . phi(k)` for the symmetric second power
`phi`, the same function is a recurrence over a state of fixed size:

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    o_t = phi(q_t)^T S_t / phi(q_t)^T z_t

and no key or value is ever cached.

**`phi` as this file lays it out.**  The published feature map lists
`u_i u_j` for `i <= j`, off-diagonal entries times sqrt 2: `d (d + 1) / 2`
numbers (8,256 at d = 128).  Here they are grouped by the circular
distance `s = (i - j) mod d`: tile `s` of `d / 2 + 1` holds `c_s * u *
roll(u, s)`, one lane a pair, with `c_0 = 1` (the squares), `c_s = sqrt 2`
for `0 < s < d / 2` (every pair at that distance once) and `c_{d/2} = 1`
(every pair at distance `d / 2` TWICE, each at weight 1: 2 * 1^2 = sqrt2^2).
`phi(u) . phi(w) = (u . w)^2` exactly as published; a tile is one product
of `u` with a lane rotation of itself, made in VMEM and never in HBM, and
the state is 65 tiles of 128 lanes at d = 128: 8,320 lanes for the
published 8,256.

**The state of one sequence in one layer**: `[Hkv, d / 2 + 1, R, d]`
float32, `R = d + 1` rounded up to whole sublanes: entry `[h, s, r, i]` is
`sum_t decay * v_ext[t, r] * phi_s(k_t)[i]` with `v_ext = [v, 1]`: rows
`0 .. d-1` are `S` transposed (value index on sublanes, feature on lanes),
row `d` is `z`, the rows after it stay zero.  At d = 128 that is `[8, 65,
136, 128]`: 36.2 MB for the published 8 x 8256 x 129 x 4 B = 34.1 MB
(+6.3%: 64 duplicated lanes, 7 idle rows).  A pool is `[slots + 1, *state]`
(`serving.kv_cache.StateCache`), and both entry points here update a
row's state IN its slot: the pool goes in and comes out, aliased, and only
the slots named are touched.

Entry points (array level; `pool` float32, everything else the model's
dtype unless said):

- `retention_prefill(q, k, v, log_g, pool, slots, fresh)`: `T` positions a
  row through the chunked form: inside a chunk the masked `(Q K^T)^2` with
  the gates' decay, across chunks `phi(Q) S` and `S <- e^b S + phi(K)^T
  (decay * V)`, the state read from the slot (zeros when `fresh`) and
  written back at the end.
- `retention_decode(q, k, v, log_g, pool, slots, valid)`: one position a
  row: the state tile read once and written once, the query heads of a K/V
  head served from that one read.

Each is a Pallas kernel on a TPU (or under `PTPU_PALLAS_INTERPRET=1`) and
the same mathematics in XLA elsewhere (`retention_fallback:<why>`,
counted like the attention gates', `pallas_ops.attention_path_counts`).
`retention_attention` is the quadratic form and `retention_recurrent` the
token-by-token one: the three are one function (tests/test_brumby.py).

A denominator that is exactly zero (every visible key orthogonal to the
query, or a padding row that has no key) reads 0 / 0; both forms give 0.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from . import pallas_ops as _po
from .pallas_ops import _count_path, _interpret

__all__ = ["phi", "state_shape", "published_state_numbers",
           "retention_attention", "retention_recurrent",
           "retention_chunked", "retention_prefill", "retention_decode",
           "PREFILL_KERNEL", "DECODE_KERNEL", "PREFILL_CHUNK"]

PREFILL_KERNEL = "retention_prefill"      # the `pallas_call` names
DECODE_KERNEL = "retention_decode"
PREFILL_CHUNK = 256                       # positions a chunk of the kernel
_VMEM_BYTES = 64 * 2 ** 20                # both kernels hold a state tile
#                                           set in and out, double-buffered
_SQRT2 = math.sqrt(2.0)
_HI = jax.lax.Precision.HIGHEST
# said outright: under a caller's `default_matmul_precision("highest")` a
# product of bfloat16 operands left to the default is refused by Mosaic
_FAST = jax.lax.Precision.DEFAULT


def _coef(s, d):
    return 1.0 if s in (0, d // 2) else _SQRT2


def _rows(d):
    return -(-(d + 1) // 8) * 8


def state_shape(num_kv_heads, head_dim):
    """One sequence's state in one layer (module docstring)."""
    if head_dim % 2:
        raise ValueError("power retention is laid out for an even head size")
    return (num_kv_heads, head_dim // 2 + 1, _rows(head_dim), head_dim)


def published_state_numbers(num_kv_heads, head_dim):
    """Numbers of the state as published: `S` and `z` over the `d (d + 1)
    / 2` distinct pairs."""
    return num_kv_heads * (head_dim * (head_dim + 1) // 2) * (head_dim + 1)


def phi(u):
    """[..., d] -> [..., d / 2 + 1, d] float32, tile `s` = `c_s * u *
    roll(u, s)`: `(phi(u) * phi(w)).sum((-2, -1)) == (u . w)^2`."""
    u = u.astype(jnp.float32)
    d = u.shape[-1]
    return jnp.stack([_coef(s, d) * u * jnp.roll(u, s, axis=-1)
                      for s in range(d // 2 + 1)], axis=-2)


def _v_ext(v, rows):
    """[..., d] -> [..., rows] float32: the value, then 1, then zeros."""
    v = v.astype(jnp.float32)
    d = v.shape[-1]
    ones = jnp.ones(v.shape[:-1] + (1,), jnp.float32)
    return jnp.concatenate(
        [v, ones, jnp.zeros(v.shape[:-1] + (rows - d - 1,), jnp.float32)],
        axis=-1)


def _ratio(acc, d):
    den = acc[..., d:d + 1]
    return acc[..., :d] / jnp.where(den == 0, 1.0, den)


def _grouped(q, hkv):
    b, t, hq, d = q.shape
    return q.reshape(b, t, hkv, hq // hkv, d)


# -- the three plain forms (XLA) --------------------------------------------

def _pair_weights(qg, k, cum):
    """`exp(b_t - b_s) * (q_t . k_s)^2` for `s <= t`, else 0: qg
    [B,T,Hkv,G,d] float32, k [B,T,Hkv,d], cum [B,T,Hkv] the gates' running
    sum -> [B,Hkv,G,T,T]."""
    t = k.shape[1]
    scores = jnp.einsum("bthgd,bshd->bhgts", qg, k.astype(jnp.float32),
                        precision=_HI)
    delta = (cum[:, :, None] - cum[:, None]).transpose(0, 3, 1, 2)
    seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None]
    return jnp.where(seen, jnp.exp(jnp.minimum(delta, 0.0)),
                     0.0)[:, :, None] * scores * scores


def retention_attention(q, k, v, log_g):
    """The quadratic form over whole sequences: q [B,T,Hq,d], k v
    [B,T,Hkv,d], log_g [B,T,Hkv] float32 -> o [B,T,Hq,d] float32.  Query
    head h reads K/V head h // (Hq / Hkv)."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    w = _pair_weights(_grouped(q.astype(jnp.float32), hkv), k,
                      jnp.cumsum(log_g.astype(jnp.float32), axis=1))
    num = jnp.einsum("bhgts,bshd->bthgd", w, v.astype(jnp.float32),
                     precision=_HI)
    den = w.sum(-1).transpose(0, 3, 1, 2)[..., None]
    return (num / jnp.where(den == 0, 1.0, den)).reshape(b, t, hq, d)


def _decode_math(q, k, v, log_g, state):
    """One position: q [B,Hq,d], k v [B,Hkv,d], log_g [B,Hkv], state
    [B, *state_shape] -> (o [B,Hq,d] float32, new state)."""
    b, hq, d = q.shape
    hkv, _, rows, _ = state.shape[1:]
    g = jnp.exp(log_g.astype(jnp.float32))[:, :, None, None, None]
    new = g * state + (phi(k)[:, :, :, None, :]
                       * _v_ext(v, rows)[:, :, None, :, None])
    acc = jnp.einsum("bhgsi,bhsri->bhgr",
                     phi(q.reshape(b, hkv, hq // hkv, d)), new,
                     precision=_HI)
    return _ratio(acc, d).reshape(b, hq, d), new


def retention_recurrent(q, k, v, log_g, state=None):
    """The recurrence, a position at a time -> (o [B,T,Hq,d] float32, the
    state after the last position)."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if state is None:
        state = jnp.zeros((b,) + state_shape(hkv, d), jnp.float32)

    def step(s, x):
        o, s = _decode_math(*x, s)
        return s, o

    state, o = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, log_g)))
    return jnp.moveaxis(o, 0, 1), state


def _pad_chunks(q, k, v, log_g, chunk):
    """Whole chunks: positions past the end carry no key, no value and a
    gate of 1, so they leave the state as it was."""
    t = q.shape[1]
    pad = -t % chunk
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for a in (q, k, v))
        log_g = jnp.pad(log_g, ((0, 0), (0, pad), (0, 0)))
    return q, k, v, log_g.astype(jnp.float32), (t + pad) // chunk


def retention_chunked(q, k, v, log_g, state=None, chunk=64):
    """The chunked form in XLA -> (o [B,T,Hq,d] float32, the state after
    the last position).  `phi` of a chunk is made in memory here: the
    kernel makes it a tile at a time."""
    b, t, hq, d = q.shape
    hkv = k.shape[2]
    if state is None:
        state = jnp.zeros((b,) + state_shape(hkv, d), jnp.float32)
    rows = state.shape[3]
    q, k, v, log_g, n = _pad_chunks(q, k, v, log_g, chunk)

    def split(a):
        return jnp.moveaxis(a.reshape((b, n, chunk) + a.shape[2:]), 1, 0)

    def step(s, x):
        qc, kc, vc, lg = x
        cum = jnp.cumsum(lg, axis=1)                       # [B,c,Hkv]
        qg = _grouped(qc.astype(jnp.float32), hkv)
        w = _pair_weights(qg, kc, cum)
        ve = _v_ext(vc, rows)                              # [B,c,Hkv,R]
        acc = jnp.einsum("bhgts,bshr->bthgr", w, ve, precision=_HI)
        acc = acc + jnp.exp(cum)[:, :, :, None, None] * jnp.einsum(
            "bthgsi,bhsri->bthgr", phi(qg), s, precision=_HI)
        last = cum[:, -1]                                  # [B,Hkv]
        vd = ve * jnp.exp(last[:, None] - cum)[..., None]
        s = (jnp.exp(last)[:, :, None, None, None] * s
             + jnp.einsum("bthr,bthsi->bhsri", vd, phi(kc), precision=_HI))
        return s, _ratio(acc, d).reshape(b, chunk, hq, d)

    state, o = jax.lax.scan(step, state, tuple(map(split,
                                                   (q, k, v, log_g))))
    return jnp.moveaxis(o, 0, 1).reshape(b, n * chunk, hq, d)[:, :t], state


# -- the gates --------------------------------------------------------------

def _kernel_ok(q, k, pool, what) -> bool:
    """Geometry gate of both kernels, counted under `what`."""
    if not (_po._on_tpu() or _interpret()):
        _count_path("retention_fallback:off_tpu")
        return False
    hq, d = q.shape[-2:]
    hkv = k.shape[-2]
    if d != 128 or hq % hkv or hq // hkv > 8:
        # a head is one lane tile and a K/V head's query heads the rows of
        # one sublane tile
        _count_path("retention_fallback:head_geometry")
        return False
    if pool.dtype != jnp.float32 or tuple(pool.shape[1:]) != state_shape(
            hkv, d):
        _count_path("retention_fallback:state_layout")
        return False
    _count_path(what)
    return True


# -- decode: one position a row ---------------------------------------------

def _decode_kernel(slot_ref, aux_ref, vb_ref, s_ref, ot_ref, so_ref,
                   *, d, tiles, fast):
    """One program a (row, K/V head).  `aux_ref` `[16, d]` float32 holds
    the head's query heads in rows `0 .. 7` (zero rows past the last),
    its key in row 8 and its gate, on every lane, in row 9; `vb_ref`
    `[rows, d]` the row's `v_ext`, each entry on every lane.  The state
    `[tiles, rows, d]` comes in through `s_ref` and goes out through
    `so_ref`, the same slot of the same pool, a tile at a time: `g * S +
    v_ext * phi_s(k)` on the VPU, stored, and from the registers that hold
    it multiplied on the MXU into the query heads' `phi_s(q)`: `[rows, d]
    x [8, d]^T`, summed over the tiles.  `ot_ref` `[rows, 8]` takes the
    result transposed: column j of row r is query head j's sum against
    `v_ext` row r (row `d` the denominator).  `fast`: that product from
    bfloat16 halves of its float32 operands, three products; else float32
    at the highest precision.  The state itself is float32 either way, and
    the call is bound by its DMAs either way (PERF.md, PR 38)."""
    from jax.experimental.pallas import tpu as pltpu

    del slot_ref
    f32 = jnp.float32
    aux = aux_ref[0, 0]
    gate = aux[9:10]
    vb = vb_ref[0, 0]
    acc = jnp.zeros((vb.shape[0], 8), f32)
    rolled = aux
    for s in range(tiles):
        ph = aux * rolled * _coef(s, d)
        new = gate * s_ref[0, 0, s] + vb * ph[8:9]
        so_ref[0, 0, s] = new
        if fast:
            # bfloat16 x 3: both operands as a high and a low half, the
            # three products that matter: 2^-16 a term.  One product of
            # the high halves leaves 2^-8 a term, and the sum's terms
            # cancel: 0.15 on outputs of scale 1 (my chip run 5, PR 38)
            halves = []
            for x in (new, ph[0:8]):
                hi = x.astype(jnp.bfloat16)
                halves.append((hi, (x - hi.astype(f32)).astype(jnp.bfloat16)))
            (n_hi, n_lo), (p_hi, p_lo) = halves
            for x, y in ((n_hi, p_hi), (n_lo, p_hi), (n_hi, p_lo)):
                acc = acc + jax.lax.dot_general(
                    x, y, (((1,), (1,)), ((), ())),
                    preferred_element_type=f32, precision=_FAST)
        else:
            acc = acc + jax.lax.dot_general(
                new, ph[0:8], (((1,), (1,)), ((), ())),
                preferred_element_type=f32, precision=_HI)
        if s + 1 < tiles:
            rolled = pltpu.roll(rolled, 1, 1)
    ot_ref[0, 0] = acc


@functools.partial(jax.jit, static_argnames=("fast", "interpret"))
def _decode_kernel_call(q, k, v, log_g, pool, slots, valid, fast=True,
                        interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hq, d = q.shape
    hkv, tiles, rows, _ = pool.shape[1:]
    groups = hq // hkv
    live = valid.astype(jnp.float32)[:, None, None]
    kf = k.astype(jnp.float32) * live
    gate = jnp.broadcast_to(jnp.exp(log_g.astype(jnp.float32))[:, :, None,
                                                                None],
                            (b, hkv, 1, d))
    aux = jnp.concatenate([
        q.astype(jnp.float32).reshape(b, hkv, groups, d),
        jnp.zeros((b, hkv, 8 - groups, d), jnp.float32),
        kf[:, :, None], gate, jnp.zeros((b, hkv, 6, d), jnp.float32)],
        axis=2)                                            # [B,Hkv,16,d]
    vb = jnp.broadcast_to(
        _v_ext(v.astype(jnp.float32) * live, rows)[..., None],
        (b, hkv, rows, d))
    state = pl.BlockSpec((1, 1, tiles, rows, d),
                         lambda r, h, slot: (slot[r], h, 0, 0, 0))

    def small(n, lanes=d):
        return pl.BlockSpec((1, 1, n, lanes), lambda r, h, slot: (r, h, 0, 0))

    # aliasing indices INCLUDE the scalar-prefetch argument (slots=0,
    # aux=1, vb=2, pool=3)
    ot, pool2 = pl.pallas_call(
        functools.partial(_decode_kernel, d=d, tiles=tiles, fast=fast),
        name=DECODE_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, hkv),
            in_specs=[small(16), small(rows), state],
            out_specs=[small(rows, 8), state]),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, rows, 8), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(slots, aux, vb, pool)
    acc = jnp.swapaxes(ot[..., :groups], -1, -2)           # [B,Hkv,G,R]
    return _ratio(acc, d).reshape(b, hq, d), pool2


def retention_decode(q, k, v, log_g, pool, slots, valid=None, fast=True):
    """One position a row against the state in its slot, the state updated
    there.  q [B,Hq,d], k v [B,Hkv,d], log_g [B,Hkv], pool `[slots + 1,
    *state_shape]` float32, slots [B] int32, valid [B] bool (a padding
    row carries no key and no value: the dropped slot it names holds zeros
    and keeps them, and it reads 0) -> (o [B,Hq,d] float32,
    pool')."""
    b = q.shape[0]
    slots = jnp.asarray(slots, jnp.int32).reshape(b)
    valid = (jnp.ones((b,), bool) if valid is None
             else jnp.asarray(valid, bool).reshape(b))
    if _kernel_ok(q, k, pool, "retention_decode_kernel"):
        return _decode_kernel_call(q, k, v, log_g, pool, slots, valid,
                                   fast=fast, interpret=_interpret())
    live = valid[:, None, None]
    # a padding row carries no key: `phi(0) = 0` leaves its slot as it was
    o, new = _decode_math(q, jnp.where(live, k, 0), jnp.where(live, v, 0),
                          log_g, pool[slots])
    return o, pool.at[slots].set(new)


# -- prefill: T positions a row through the chunked form ---------------------

def _prefill_kernel(slot_ref, q_ref, k_ref, v_ref, vdt_ref, bq_ref, bk_ref,
                    s_ref, o_ref, so_ref, qf, qr, kf, kr, num, den,
                    *, groups, d, tiles, chunk, fresh, fast):
    """One program a (row, K/V head, chunk); the chunks of a head run in
    order and the head's state `[tiles, rows, d]` rests in `so_ref`'s
    block between them (its index does not move with the chunk), read
    from the slot - or zeroed, `fresh` - at the first and written back
    after the last.

    q_ref `[groups * chunk, d]`: the head's query heads, one after the
    other; k_ref v_ref `[chunk, d]`; vdt_ref `[rows, chunk]`: `v_ext`
    transposed, each position times `exp(b_end - b_s)`; bq_ref `[groups *
    chunk, d]`: `b_t` of a query row on every lane; bk_ref `[8, chunk]`:
    `b_s` along the lanes.  `b` is the running sum of the log gate from
    the chunk's start.

    In the chunk: `A = (Q K^T)^2 * exp(b_t - b_s)` under the causal mask,
    `A V` and `A 1`.  Across chunks, a tile `s` of 65 at a time: `phi_s(Q)`
    against the state's tile (numerator on the MXU, the `z` row on the
    VPU), then the tile's update `e^{b_end} S + vdt phi_s(K)`.  `qr`, `kr`
    hold `roll(Q, s)`, `roll(K, s)`, turned one lane a tile.  `fast`: the
    MXU's operands in bfloat16 (sums in float32); else float32 at the
    highest precision."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del slot_ref
    i = pl.program_id(2)
    f32 = jnp.float32

    @pl.when(i == 0)
    def _start():
        if fresh:
            so_ref[...] = jnp.zeros(so_ref.shape, f32)
        else:
            so_ref[...] = s_ref[...]

    def mm(a, b, dims):
        if fast:
            return jax.lax.dot_general(
                a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                (dims, ((), ())), preferred_element_type=f32,
                precision=_FAST)
        return jax.lax.dot_general(a.astype(f32), b.astype(f32),
                                   (dims, ((), ())),
                                   preferred_element_type=f32, precision=_HI)

    q, k, v = q_ref[0, 0, 0], k_ref[0, 0, 0], v_ref[0, 0, 0]
    bq = bq_ref[0, 0, 0]                                   # [G*c, d]
    gc = groups * chunk
    # the scores from the activations as they are: exact in one pass
    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32,
                             precision=_HI if q.dtype == f32 else _FAST)
    t_of = jax.lax.broadcasted_iota(jnp.int32, (gc, d), 0) & (chunk - 1)
    parts = []
    for j in range(chunk // d):
        lanes = slice(j * d, (j + 1) * d)
        s_of = j * d + jax.lax.broadcasted_iota(jnp.int32, (gc, d), 1)
        decay = jnp.exp(jnp.minimum(bq - bk_ref[0, 0, 0, 0:1, lanes], 0.0))
        parts.append(jnp.where(t_of >= s_of, decay, 0.0)
                     * sc[:, lanes] * sc[:, lanes])
    a = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    num_in = mm(a, v, ((1,), (0,)))                        # [G*c, d]
    den_in = jnp.sum(a, axis=1, keepdims=True)             # [G*c, 1]

    qf[...] = q.astype(f32)
    qr[...] = qf[...]
    kf[...] = k.astype(f32)
    kr[...] = kf[...]
    num[...] = jnp.zeros(num.shape, f32)
    den[...] = jnp.zeros(den.shape, f32)
    end = jnp.exp(bq[chunk - 1:chunk])                     # [1, d]: e^b_end
    vdt = vdt_ref[0, 0, 0]                                 # [rows, chunk]

    def tile(s, carry):
        c = jnp.where((s == 0) | (s == d // 2), 1.0, _SQRT2).astype(f32)
        pq = qf[...] * qr[...] * c
        pk = kf[...] * kr[...] * c
        st = so_ref[0, 0, s]                               # [rows, d]
        num[...] += mm(pq, st[:d], ((1,), (1,)))
        den[...] += pq * st[d:d + 1]
        so_ref[0, 0, s] = end * st + mm(vdt, pk, ((1,), (0,)))
        qr[...] = pltpu.roll(qr[...], 1, 1)
        kr[...] = pltpu.roll(kr[...], 1, 1)
        return carry

    jax.lax.fori_loop(0, tiles, tile, 0)
    from_start = jnp.exp(bq)
    total = den_in + from_start[:, 0:1] * jnp.sum(den[...], axis=1,
                                                  keepdims=True)
    o_ref[0, 0, 0] = ((num_in + from_start * num[...])
                      / jnp.where(total == 0, 1.0, total)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("fresh", "chunk", "fast",
                                             "interpret"))
def _prefill_kernel_call(q, k, v, log_g, pool, slots, fresh, chunk,
                         fast=True, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, hq, d = q.shape
    hkv, tiles, rows, _ = pool.shape[1:]
    groups = hq // hkv
    if chunk % d or chunk & (chunk - 1):
        raise ValueError("a chunk is a power of two of whole lane tiles")
    q, k, v, log_g, n = _pad_chunks(q, k, v, log_g, chunk)
    cum = jnp.cumsum(log_g.reshape(b, n, chunk, hkv), axis=2)
    cum = cum.transpose(0, 3, 1, 2)                        # [B,Hkv,n,c]
    qk = q.reshape(b, n, chunk, hkv, groups, d).transpose(
        0, 3, 1, 4, 2, 5).reshape(b, hkv, n, groups * chunk, d)
    kk, vv = (a.reshape(b, n, chunk, hkv, d).transpose(0, 3, 1, 2, 4)
              for a in (k, v))
    bq = jnp.broadcast_to(
        jnp.tile(cum, (1, 1, 1, groups))[..., None],
        (b, hkv, n, groups * chunk, d))
    bk = jnp.broadcast_to(cum[:, :, :, None], (b, hkv, n, 8, chunk))
    vdt = jnp.swapaxes(
        _v_ext(vv, rows) * jnp.exp(cum[..., -1:] - cum)[..., None], -1, -2)
    if fast:
        vdt = vdt.astype(jnp.bfloat16)

    def per_chunk(*block):
        return pl.BlockSpec((1, 1, 1) + block,
                            lambda r, h, i, slot: (r, h, i, 0, 0))

    state = pl.BlockSpec((1, 1, tiles, rows, d),
                         lambda r, h, i, slot: (slot[r], h, 0, 0, 0))
    gc = groups * chunk
    f32 = jnp.float32
    # aliasing indices INCLUDE the scalar-prefetch argument (slots=0,
    # q=1 .. bk=6, pool=7)
    o, pool2 = pl.pallas_call(
        functools.partial(_prefill_kernel, groups=groups, d=d, tiles=tiles,
                          chunk=chunk, fresh=fresh, fast=fast),
        name=PREFILL_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, hkv, n),
            in_specs=[per_chunk(gc, d), per_chunk(chunk, d),
                      per_chunk(chunk, d), per_chunk(rows, chunk),
                      per_chunk(gc, d), per_chunk(8, chunk),
                      # a fresh state is never read: leave it where it is
                      pl.BlockSpec(memory_space=pl.ANY) if fresh else state],
            out_specs=[per_chunk(gc, d), state],
            scratch_shapes=[pltpu.VMEM((gc, d), f32),
                            pltpu.VMEM((gc, d), f32),
                            pltpu.VMEM((chunk, d), f32),
                            pltpu.VMEM((chunk, d), f32),
                            pltpu.VMEM((gc, d), f32),
                            pltpu.VMEM((gc, d), f32)]),
        out_shape=[jax.ShapeDtypeStruct((b, hkv, n, gc, d), q.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(slots, qk, kk, vv, vdt, bq, bk, pool)
    o = o.reshape(b, hkv, n, groups, chunk, d).transpose(
        0, 2, 4, 1, 3, 5).reshape(b, n * chunk, hq, d)
    return o[:, :t], pool2


def retention_prefill(q, k, v, log_g, pool, slots, fresh, chunk=None,
                      fast=True):
    """`T` positions a row through the chunked form, from the state in the
    row's slot (zeros when `fresh`: a sequence's start) to the state after
    the last position, written to the slot.  q [B,T,Hq,d], k v
    [B,T,Hkv,d], log_g [B,T,Hkv], pool `[slots + 1, *state_shape]`
    float32, slots [B] int32 -> (o [B,T,Hq,d] in q's dtype, pool')."""
    b = q.shape[0]
    slots = jnp.asarray(slots, jnp.int32).reshape(b)
    if _kernel_ok(q, k, pool, "retention_prefill_kernel"):
        return _prefill_kernel_call(q, k, v, log_g, pool, slots,
                                    fresh=bool(fresh),
                                    chunk=chunk or PREFILL_CHUNK, fast=fast,
                                    interpret=_interpret())
    state = None if fresh else pool[slots]
    o, new = retention_chunked(q, k, v, log_g, state,
                               chunk=chunk or min(64, q.shape[1]))
    return o.astype(q.dtype), pool.at[slots].set(new)
