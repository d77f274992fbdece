"""Mamba-2's state-space mixer core, the SSD recurrence ("Transformers are
SSMs", arXiv:2405.21060), as Falcon-H1's mixers run it (models/falcon_h1.py):
head `h` of `H` reads B/C group `g = h // (H / G)`, and its state `S` of
`P x N` numbers (head size x state size) follows

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_{g,t}^T
    y_t = S_t C_{g,t}

with `dt_t > 0` and `A_h < 0` a head, `x_t` [P], `B_{g,t}`, `C_{g,t}` [N].
The skip term `D_h x_t` is the caller's.

**The state of one sequence in one layer**: `[H, N, P]` float32, each
head's `S` TRANSPOSED (state index on sublanes, the head's lanes on
lanes): 32 x 256 x 128 x 4 B = 4,194,304 B at Falcon-H1-34B's widths.  A
pool is `[slots + 1, H, N, P]` (`serving.kv_cache.StateCache`), and both
entry points update a row's state IN its slot: the pool goes in and comes
out aliased, and only the slots named are touched.

Entry points (array level; x [.., H, P] and B, C [.., G, N] in the model's
dtype, dt [.., H] and A [H] float32, the pool float32):

- `ssd_prefill(x, dt, A, B, C, pool, slots, fresh)`: `T` positions a row
  through the chunked form (chunks of `mamba_chunk_size`): inside a chunk
  `(C B^T)` under the chunk's causal decay times `dt x`, across chunks
  `C S` decayed from the chunk's start and `S <- e^{a_end} S + B^T (decay
  * dt x)`; the state read from the slot (zeros when `fresh`) and written
  back after the last chunk.
- `ssd_decode(x, dt, A, B, C, pool, slots, valid)`: one position a row:
  a block of 8 heads' states read once and written once by slot index.

Each is a Pallas kernel on a TPU (or under `PTPU_PALLAS_INTERPRET=1`),
named `ssd_prefill` / `ssd_decode`, and the same mathematics in XLA
elsewhere (`ssd_fallback:<why>`, counted like the attention gates',
`pallas_ops.attention_path_counts`): `ssd_chunked` the chunked form and
`_decode_math` the one-position form in XLA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import pallas_ops as _po
from .pallas_ops import _count_path, _interpret

__all__ = ["state_shape", "ssd_chunked", "ssd_prefill",
           "ssd_decode", "PREFILL_KERNEL", "DECODE_KERNEL"]

PREFILL_KERNEL = "ssd_prefill"            # the `pallas_call` names
DECODE_KERNEL = "ssd_decode"
HEAD_BLOCK = 8                            # heads a decode program moves
_LANES = 128
_VMEM_BYTES = 32 * 2 ** 20
_HI = jax.lax.Precision.HIGHEST
# said outright: under a caller's `default_matmul_precision("highest")` a
# product of bfloat16 operands left to the default is refused by Mosaic
_FAST = jax.lax.Precision.DEFAULT
_F32 = jnp.float32


def state_shape(num_heads, head_dim, d_state):
    """One sequence's state in one layer (module docstring)."""
    return (num_heads, d_state, head_dim)


def _heads(a, h):
    """[.., G, N] -> [.., H, N]: each group's row for each of its heads."""
    return jnp.repeat(a.astype(_F32), h // a.shape[-2], axis=-2)


# -- the two plain forms (XLA) ----------------------------------------------

def _decode_math(x, dt, A, B, C, state):
    """One position: x [B,H,P], dt [B,H], B C [B,G,N], state [B,H,N,P]
    -> (y [B,H,P] float32, new state)."""
    h = x.shape[1]
    dtf = dt.astype(_F32)
    decay = jnp.exp(dtf * A.astype(_F32))[..., None, None]
    xdt = x.astype(_F32) * dtf[..., None]
    new = decay * state + _heads(B, h)[..., :, None] * xdt[..., None, :]
    y = jnp.einsum("bhn,bhnp->bhp", _heads(C, h), new, precision=_HI)
    return y, new


def _chunk_inputs(x, dt, A, B, C, chunk):
    """`dt x` float32, the log decay `dt A`, B and C, each padded to whole
    chunks: a position past the end carries `dt` 0, so it adds nothing to
    the state and does not decay it."""
    t = x.shape[1]
    pad = -t % chunk
    dtf = dt.astype(_F32)
    xdt = x.astype(_F32) * dtf[..., None]
    a = dtf * A.astype(_F32)
    if pad:
        def p(v):
            return jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        xdt, a, B, C = p(xdt), p(a), p(B), p(C)
    return xdt, a, B, C, (t + pad) // chunk


def ssd_chunked(x, dt, A, B, C, state=None, chunk=64):
    """The chunked form in XLA -> (y [B,T,H,P] float32, the state after
    the last position)."""
    b, t, h, p = x.shape
    g, n = B.shape[-2:]
    j = h // g
    if state is None:
        state = jnp.zeros((b,) + state_shape(h, p, n), _F32)
    xdt, a, B, C, nc = _chunk_inputs(x, dt, A, B, C, chunk)

    def split(v):
        return jnp.moveaxis(v.reshape((b, nc, chunk) + v.shape[2:]), 1, 0)

    seen = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(s, inp):
        xc, ac, bc, cc = inp
        bc, cc = bc.astype(_F32), cc.astype(_F32)
        cum = jnp.cumsum(ac, axis=1).reshape(b, chunk, g, j)   # [B,L,G,J]
        cg = cum.transpose(0, 2, 3, 1)                         # [B,G,J,L]
        delta = cg[..., :, None] - cg[..., None, :]            # [..,t,s]
        decay = jnp.where(seen, jnp.exp(jnp.minimum(delta, 0.0)), 0.0)
        scores = jnp.einsum("btgn,bsgn->bgts", cc, bc, precision=_HI)
        xg = xc.reshape(b, chunk, g, j, p)
        y = jnp.einsum("bgjts,bsgjp->btgjp", scores[:, :, None] * decay,
                       xg, precision=_HI)
        sg = s.reshape(b, g, j, n, p)
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "btgn,bgjnp->btgjp", cc, sg, precision=_HI)
        last = cum[:, -1]                                      # [B,G,J]
        w = jnp.exp(last[:, None] - cum)[..., None] * xg       # [B,L,G,J,P]
        sg = (jnp.exp(last)[..., None, None] * sg
              + jnp.einsum("bsgn,bsgjp->bgjnp", bc, w, precision=_HI))
        return sg.reshape(b, h, n, p), y.reshape(b, chunk, h, p)

    state, y = jax.lax.scan(step, state, tuple(map(split, (xdt, a, B, C))))
    return jnp.moveaxis(y, 0, 1).reshape(b, nc * chunk, h, p)[:, :t], state


# -- the gate ---------------------------------------------------------------

def _kernel_ok(x, B, pool, what, chunk=None) -> bool:
    """Geometry gate of both kernels, counted under `what`."""
    if not (_po._on_tpu() or _interpret()):
        _count_path("ssd_fallback:off_tpu")
        return False
    h, p = x.shape[-2:]
    g, n = B.shape[-2:]
    if (p != _LANES or n % _LANES or h % g or (h // g) % HEAD_BLOCK
            or chunk not in (None, _LANES)):
        # a head is one lane tile, the state's rows whole lane tiles, a
        # decode program's heads one group's, and a chunk's positions one
        # lane tile (its decay is laid along the lanes)
        _count_path("ssd_fallback:geometry")
        return False
    if pool.dtype != _F32 or tuple(pool.shape[1:]) != state_shape(h, p, n):
        _count_path("ssd_fallback:state_layout")
        return False
    _count_path(what)
    return True


# -- decode: one position a row ---------------------------------------------

def _decode_kernel(slot_ref, aux_ref, bc_ref, bct_ref, s_ref, y_ref, so_ref,
                   *, hb):
    """One program a (row, block of `hb` heads of one group).  `aux_ref`
    `[2 hb, P]`: the heads' `dt x` in rows `0 .. hb-1`, their decay
    `exp(dt A)` on every lane in rows `hb ..`; `bc_ref` `[8, N]` the
    group's B in row 0 and C in row 1 (zeros below), `bct_ref` the same
    transposed.  A head's state `[N, P]` comes in through `s_ref` and goes
    out through `so_ref`, the same slot of the same pool: `decay * S + B
    (dt x)^T`, the outer product as one MXU product of `bct` with a matrix
    whose only row is `dt x`, then `C^T S` as row 1 of `bc S`.  Products
    in float32 at the highest precision: the state is float32."""
    del slot_ref
    aux = aux_ref[0, 0]
    bc, bct = bc_ref[0, 0], bct_ref[0, 0]
    p = aux.shape[1]
    first = jax.lax.broadcasted_iota(jnp.int32, (8, p), 0) == 0
    row = jax.lax.broadcasted_iota(jnp.int32, (hb, p), 0)
    out = jnp.zeros((hb, p), _F32)
    for j in range(hb):
        e = jnp.where(first, aux[j:j + 1], 0.0)
        new = aux[hb + j:hb + j + 1] * s_ref[0, j] + jax.lax.dot_general(
            bct, e, (((1,), (0,)), ((), ())), preferred_element_type=_F32,
            precision=_HI)
        so_ref[0, j] = new
        y = jax.lax.dot_general(bc, new, (((1,), (0,)), ((), ())),
                                preferred_element_type=_F32, precision=_HI)
        out = jnp.where(row == j, y[1:2], out)
    y_ref[0, 0] = out


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_kernel_call(x, dt, A, B, C, pool, slots, valid, interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, p = x.shape
    g, n = B.shape[-2:]
    hb, hpg = HEAD_BLOCK, h // g
    nb = h // hb
    # a padding row moves nothing: dt 0 adds nothing and decays nothing
    dtf = dt.astype(_F32) * valid.astype(_F32)[:, None]
    xdt = x.astype(_F32) * dtf[..., None]
    decay = jnp.broadcast_to(jnp.exp(dtf * A.astype(_F32))[..., None],
                             (b, h, p))
    aux = jnp.concatenate([xdt.reshape(b, nb, hb, p),
                           decay.reshape(b, nb, hb, p)], axis=2)
    bc = jnp.concatenate([B.astype(_F32)[:, :, None],
                          C.astype(_F32)[:, :, None],
                          jnp.zeros((b, g, 6, n), _F32)], axis=2)
    state = pl.BlockSpec((1, hb, n, p),
                         lambda r, k, slot: (slot[r], k, 0, 0))

    def by_group(*block):
        return pl.BlockSpec((1, 1) + block,
                            lambda r, k, slot: (r, k * hb // hpg, 0, 0))

    # aliasing indices INCLUDE the scalar-prefetch argument (slots=0,
    # aux=1, bc=2, bct=3, pool=4)
    y, pool2 = pl.pallas_call(
        functools.partial(_decode_kernel, hb=hb),
        name=DECODE_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nb),
            in_specs=[pl.BlockSpec((1, 1, 2 * hb, p),
                                   lambda r, k, slot: (r, k, 0, 0)),
                      by_group(8, n), by_group(n, 8), state],
            out_specs=[pl.BlockSpec((1, 1, hb, p),
                                    lambda r, k, slot: (r, k, 0, 0)),
                       state]),
        out_shape=[jax.ShapeDtypeStruct((b, nb, hb, p), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(slots, aux, bc, jnp.swapaxes(bc, -1, -2), pool)
    return y.reshape(b, h, p), pool2


def ssd_decode(x, dt, A, B, C, pool, slots, valid=None):
    """One position a row against the state in its slot, the state updated
    there.  x [B,H,P], dt [B,H], A [H], B C [B,G,N], pool `[slots + 1,
    *state_shape]` float32, slots [B] int32, valid [B] bool (a padding row
    leaves the dropped slot it names as it was) -> (y [B,H,P] float32,
    pool')."""
    b = x.shape[0]
    slots = jnp.asarray(slots, jnp.int32).reshape(b)
    valid = (jnp.ones((b,), bool) if valid is None
             else jnp.asarray(valid, bool).reshape(b))
    if _kernel_ok(x, B, pool, "ssd_decode_kernel"):
        return _decode_kernel_call(x, dt, A, B, C, pool, slots, valid,
                                   interpret=_interpret())
    y, new = _decode_math(x, jnp.where(valid[:, None], dt, 0), A, B, C,
                          pool[slots])
    return y, pool.at[slots].set(new)


# -- prefill: T positions a row through the chunked form ---------------------

def _prefill_kernel(slot_ref, xdt_ref, cq_ref, ck_ref, bt_ref, c_ref, s_ref,
                    y_ref, so_ref, *, fresh):
    """One program a (row, head, chunk); the chunks of a head run in order
    and the head's state `[N, P]` rests in `so_ref`'s block between them
    (its index does not move with the chunk), read from the slot - or
    zeroed, `fresh` - at the first and written back after the last.

    xdt_ref `[L, P]`: `dt x` of the chunk's positions; cq_ref `[L, 128]`:
    `a_t`, the running sum of `dt A` from the chunk's start, of a row on
    every lane; ck_ref `[8, L]`: the same along the lanes (row 0); bt_ref
    `[N, L]`: the group's B transposed; c_ref `[L, N]` its C.  `L` is one
    lane tile, so a `[L, L]` matrix of decays is made from the two.

    In the chunk: `(C B^T) * exp(a_t - a_s)` under the causal mask, times
    `dt x`.  From the state: `exp(a_t) C S`.  The state's pass:
    `exp(a_end) S + B^T (exp(a_end - a_s) dt x)`.  `C B^T` from the
    activations as they are (exact in one pass); the products that meet
    the float32 state or the decays in float32 at the highest precision."""
    from jax.experimental import pallas as pl

    del slot_ref
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _start():
        if fresh:
            so_ref[...] = jnp.zeros(so_ref.shape, _F32)
        else:
            so_ref[...] = s_ref[...]

    def mm(a, b, prec=_HI):
        return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                                   preferred_element_type=_F32,
                                   precision=prec)

    xdt, cq = xdt_ref[0, 0, 0], cq_ref[0, 0, 0]
    bt, c = bt_ref[0, 0, 0], c_ref[0, 0, 0]
    n = cq.shape[0]
    t_of = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    s_of = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    decay = jnp.where(t_of >= s_of,
                      jnp.exp(jnp.minimum(cq - ck_ref[0, 0, 0, 0:1], 0.0)),
                      0.0)
    scores = mm(c, bt, _HI if c.dtype == _F32 else _FAST)
    s0 = so_ref[0, 0]
    y = mm(scores * decay, xdt) + jnp.exp(cq) * mm(c.astype(_F32), s0)
    last = cq[n - 1:n]
    so_ref[0, 0] = jnp.exp(last) * s0 + mm(bt.astype(_F32),
                                           jnp.exp(last - cq) * xdt)
    y_ref[0, 0, 0] = y


@functools.partial(jax.jit, static_argnames=("fresh", "chunk", "interpret"))
def _prefill_kernel_call(x, dt, A, B, C, pool, slots, fresh, chunk,
                         interpret=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, t, h, p = x.shape
    g, n = B.shape[-2:]
    hpg = h // g
    xdt, a, B, C, nc = _chunk_inputs(x, dt, A, B, C, chunk)
    xdt = xdt.reshape(b, nc, chunk, h, p).transpose(0, 3, 1, 2, 4)
    cum = jnp.cumsum(a.reshape(b, nc, chunk, h), axis=2).transpose(
        0, 3, 1, 2)                                        # [B,H,nc,L]
    cq = jnp.broadcast_to(cum[..., None], (b, h, nc, chunk, _LANES))
    ck = jnp.broadcast_to(cum[..., None, :], (b, h, nc, 8, chunk))
    bt = B.reshape(b, nc, chunk, g, n).transpose(0, 3, 1, 4, 2)
    cc = C.reshape(b, nc, chunk, g, n).transpose(0, 3, 1, 2, 4)

    def per_head(*block):
        return pl.BlockSpec((1, 1, 1) + block,
                            lambda r, k, i, slot: (r, k, i, 0, 0))

    def per_group(*block):
        return pl.BlockSpec((1, 1, 1) + block,
                            lambda r, k, i, slot: (r, k // hpg, i, 0, 0))

    state = pl.BlockSpec((1, 1, n, p),
                         lambda r, k, i, slot: (slot[r], k, 0, 0))
    # aliasing indices INCLUDE the scalar-prefetch argument (slots=0,
    # xdt=1 .. cc=5, pool=6)
    y, pool2 = pl.pallas_call(
        functools.partial(_prefill_kernel, fresh=fresh),
        name=PREFILL_KERNEL,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, h, nc),
            in_specs=[per_head(chunk, p), per_head(chunk, _LANES),
                      per_head(8, chunk), per_group(n, chunk),
                      per_group(chunk, n),
                      # a fresh state is never read: leave it where it is
                      pl.BlockSpec(memory_space=pl.ANY) if fresh else state],
            out_specs=[per_head(chunk, p), state]),
        out_shape=[jax.ShapeDtypeStruct((b, h, nc, chunk, p), _F32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
    )(slots, xdt, cq, ck, bt, cc, pool)
    y = y.transpose(0, 2, 3, 1, 4).reshape(b, nc * chunk, h, p)
    return y[:, :t], pool2


def ssd_prefill(x, dt, A, B, C, pool, slots, fresh, chunk=_LANES):
    """`T` positions a row through the chunked form, from the state in the
    row's slot (zeros when `fresh`: a sequence's start) to the state after
    the last position, written to the slot.  x [B,T,H,P], dt [B,T,H], A
    [H], B C [B,T,G,N], pool `[slots + 1, *state_shape]` float32, slots [B]
    int32 -> (y [B,T,H,P] float32, pool')."""
    b = x.shape[0]
    slots = jnp.asarray(slots, jnp.int32).reshape(b)
    if _kernel_ok(x, B, pool, "ssd_prefill_kernel", chunk):
        return _prefill_kernel_call(x, dt, A, B, C, pool, slots,
                                    fresh=bool(fresh), chunk=chunk,
                                    interpret=_interpret())
    y, new = ssd_chunked(x, dt, A, B, C, None if fresh else pool[slots],
                         chunk=min(chunk, x.shape[1]))
    return y, pool.at[slots].set(new)
