"""Real-TPU kernel checks, run through the chip tool and nothing else:

    chiprun -- python scripts/onchip_checks.py
    python scripts/onchip_checks.py --aot     # no chip: compile only
    chiprun -- python scripts/onchip_checks.py --writes [--tree DIR]
    chiprun -- python scripts/onchip_checks.py --sampler
    chiprun -- python scripts/onchip_checks.py --retention

Every Pallas kernel on the main path goes through actual Mosaic
compilation and is compared with its XLA reference at the widths of both
listed GPT models — H12xD64 (hidden 768) and H16xD128 (hidden 2048).
Interpret mode (the CPU suite) validates numerics but skips every Mosaic
legality rule — block shapes' (8,128) divisibility, memref slice/tiling
alignment, transpose legalization — the class that has failed at every
first hardware contact so far.  One "OK <name>" line per check; any
failure exits non-zero.

`--writes` runs the KV pool writers alone (`check_writes`) and prints what
one write and one layer of the C > 1 fallback cost; `--tree DIR` takes
`paddle_tpu` from another checkout (the parent, to compare with: same
inputs, in the pool shape that tree keeps).

`--afmoe` runs the grouped-head and windowed calls of both attention
kernels at the published afmoe shapes (48 query heads over 8 K/V heads of
128, window 4096, block 64) against their XLA fallbacks, and then a
5-layer afmoe engine (the benchmark's `trinity-large-ep8-l5`: prefill of
4,224 tokens and 8 decode steps) against `benchmark/lib/reference_afmoe`,
with what the reference's deliberate faults read against the same tokens.
The default run includes the kernel calls, not the engine leg.

`--cells` times the decode kernel alone at the calls of the four serving
cells (chat: 16 rows x 16 heads over `[2048,16,2048]`; docqa: 8 rows x 32
heads over `[1024,16,4096]`; afmoe: 32 rows x 48-over-8 heads over
`[2080,64,1024]` with the window and `[4224,64,1024]` without; lfm2: 64
rows x 32-over-8 heads of 64 lanes over `[4608,64,512]`), checks
each against the XLA fallback, and prints ms a call, us a block and GB/s
of live K/V; then the latent decode kernel at the fifth cell's call
(mistral4: 64 rows x 32 absorbed heads of 320 over the ONE pool
`[12288,64,384]`): ms a call, us a tile of 256 rows, GB/s of latents at
the published 640 B a row (`--latent`: that call alone).  `--cells --split`
times it again with its matrix products cut out (DMA only), with its DMAs
cut out (math only) and with rows of one token (what a row costs before
its stream), and, where the call takes the per-head body, once more with
the segment-indicator body forced; the latent call, in place of the last
two, over a key of two lane tiles (what a split layout could cost at
least).
`--tree DIR` as above: the parent's kernel, same inputs.

`--sampler` runs the engine's sample program (`serving.engine.
_sample_program`) at the four serving cells' logits (`[64,65536]`,
`[16,50304]`, `[32,25024]`, `[8,50304]`) over three batches - every row
greedy, every row temperature only, every row `top_k=50, top_p=0.9` -
beside the program it replaced (two vocabulary sorts a row whatever the
rows ask for: `tests/test_sampler.py::two_sort_row`): tokens and keys
equal, then us a call of each on the device (the profiler's `XLA Modules`
line).  No cell sends sampled
traffic, so the two branches that draw are measured here alone.

`--ties` counts, at the fifth cell's configuration and seeded weights, how
far the program's choices lie under the float32 reference
(`benchmark/lib/reference_mistral4.py`) with and without the reference's
ties: four sequences of 12,288 uniform tokens through the program's
whole-sequence forward, each position's argmax read against the
reference's main path alone and against the least over its branches, the
first sequence under each deliberate fault as well; then one
`greedy_margins` row at the cell's 17,408 positions, timed.

`--retention` runs both power-retention kernels (`ops/power_retention.py`)
at the Brumby cell's calls - decode: 24 rows x 40-over-8 heads of 128 over
a `[25, 8, 65, 136, 128]` float32 pool; prefill: one row of 1,024 positions
from a zero state and 1,024 more from the state that left - against the
same mathematics in XLA at the highest matmul precision (outputs and the
states written), then ms a call of each with the pool donated, as the
engine calls them, and the GB/s of published state a decode call moves.  With `--aot`
it compiles the same calls and stops.

`--aot` needs no chip: it compiles each kernel for a v5e topology
description with the local libtpu (`jax.experimental.topologies`) and
stops there.  That catches Mosaic refusals from a CPU-only sandbox; it
says nothing about numerics or DMA semaphore balance, which only the
chip run does.
"""
import functools
import os
import sys
import threading
import time

sys.path.insert(0, os.path.abspath(sys.argv[sys.argv.index("--tree") + 1])
                if "--tree" in sys.argv else
                os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

WIDTHS = {"hd768": (12, 64), "hd2048": (16, 128)}
AOT = "--aot" in sys.argv[1:]
WRITES_ONLY = "--writes" in sys.argv[1:]
AFMOE_ONLY = "--afmoe" in sys.argv[1:]
CELLS_ONLY = "--cells" in sys.argv[1:]
LATENT_ONLY = "--latent" in sys.argv[1:]
SPLIT = "--split" in sys.argv[1:]
SAMPLER_ONLY = "--sampler" in sys.argv[1:]
TIES_ONLY = "--ties" in sys.argv[1:]
RETENTION_ONLY = "--retention" in sys.argv[1:]
AFMOE = dict(hq=48, hkv=8, d=128, window=4096, bs=64)
_AOT_SHARDING = None


class _Watchdog:
    """A kernel that deadlocks on the chip blocks the host forever inside
    the runtime; name the check and end the process instead of burning the
    chip call (and its strike) down to the tool's limit."""

    def __init__(self, name, secs=240.0):
        self._timer = threading.Timer(secs, self._fire, (name, secs))
        self._timer.daemon = True

    @staticmethod
    def _fire(name, secs):
        print(f"HANG {name}: no result after {secs:.0f}s", flush=True)
        os._exit(2)

    def __enter__(self):
        self._timer.start()

    def __exit__(self, *exc):
        self._timer.cancel()
        return False


def _compile_only(fn, args):
    """--aot: compile `fn` for the v5e topology, run nothing."""
    import jax

    structs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=_AOT_SHARDING), list(args))
    jax.jit(fn).lower(*structs).compile()


def _check(name, fn, ref, args, tol=5e-2):
    """`fn(*args)` through Mosaic on the chip against `ref(*args)` in XLA
    (same pytree of outputs); under --aot, compile `fn` and stop."""
    import jax

    rel = []
    if AOT:
        _compile_only(fn, args)
    else:
        got = jax.tree_util.tree_leaves(jax.jit(fn)(*args))
        want = jax.tree_util.tree_leaves(jax.jit(ref)(*args))
        for g, w in zip(got, want):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            assert np.isfinite(g).all(), name
            np.testing.assert_allclose(g, w, rtol=tol, atol=tol, err_msg=name)
            rel.append(float(np.sqrt(((g - w) ** 2).mean()
                                     / max((w ** 2).mean(), 1e-30))))
    print(f"OK {name}" + (f" (relative rms error {max(rel):.4f})"
                          if rel else ""), flush=True)


def _randn(rng, shape, dtype):
    import jax.numpy as jnp

    return jnp.asarray(rng.randn(*shape), dtype)


def check_flash(wname, h, d):
    """Flash fwd+bwd at the training shape, fwd at the prefill shapes, and
    the masked / cross-attention variants, against XLA attention."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_ops import flash_attention_arrays, mha_reference

    rng = np.random.RandomState(0)
    b, s = (8, 1024) if h == 12 else (2, 2048)

    def fwd(q, k, v):
        return flash_attention_arrays(q, k, v, is_causal=True)

    def ref(q, k, v):
        return mha_reference(q, k, v, None, True)

    def grads(fn):
        return jax.grad(lambda q, k, v: (fn(q, k, v).astype(jnp.float32)
                                         * 0.01).sum(), argnums=(0, 1, 2))

    for tag, bb, ss in (("train", b, s), ("prefill128", 1, 128),
                        ("prefill256", 1, 256)):
        qkv = [_randn(rng, (bb, ss, h, d), jnp.bfloat16) for _ in range(3)]
        _check(f"flash_fwd_{tag}_{wname}", fwd, ref, qkv)
    _check(f"flash_bwd_{wname}", grads(fwd), grads(ref),
           [_randn(rng, (b, s, h, d), jnp.bfloat16) for _ in range(3)])

    # additive mask blocking a band of keys, then cross attention sk != sq
    sm = 256
    q, k, v = (_randn(rng, (2, sm, h, d), jnp.bfloat16) for _ in range(3))
    band = (jnp.arange(sm)[None, :] > 64) & (jnp.arange(sm)[None, :] < 128)
    mask = jnp.broadcast_to(jnp.where(band, -1e30, 0.0)[None, None].astype(
        jnp.float32), (2, 1, sm, sm))
    _check(f"flash_masked_{wname}",
           lambda q, k, v, m: flash_attention_arrays(q, k, v, m, False),
           lambda q, k, v, m: mha_reference(q, k, v, m), [q, k, v, mask])
    _check(f"flash_cross_{wname}",
           lambda q, k, v: flash_attention_arrays(q, k, v, None, False),
           lambda q, k, v: mha_reference(q, k, v),
           [q, k[:, :128], v[:, :128]])


def check_flash_decode(wname, h, d):
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas_ops import flash_decode_arrays, mha_reference

    rng = np.random.RandomState(2)
    b, s_max = 8, (1024 if h == 12 else 2048)
    length = s_max - 37
    q = _randn(rng, (b, 1, h, d), jnp.bfloat16)
    kc, vc = (_randn(rng, (b, s_max, h * d), jnp.bfloat16) for _ in range(2))
    _check(f"flash_decode_{wname}",
           lambda q, k, v: flash_decode_arrays(q, k, v, jnp.int32(length)),
           lambda q, k, v: mha_reference(
               q, k[:, :length].reshape(b, length, h, d),
               v[:, :length].reshape(b, length, h, d)), [q, kc, vc])


def check_ragged(wname, h, d, quant):
    """The serving decode kernel against its own XLA fallback
    (PTPU_RAGGED_KERNEL=0): mixed lengths incl. a block-aligned row, a
    one-token row and a padding row; outputs AND the updated pools."""
    import jax.numpy as jnp
    from paddle_tpu.ops import ragged_paged_attention as rp

    rng = np.random.RandomState(3 + quant)
    bs = 32 if quant else 16
    b, maxb = 8, (1024 if h == 12 else 2048) // bs
    nb = b * maxb
    perm = rng.permutation(nb).astype(np.int32).reshape(b, maxb)
    lens = np.asarray([bs + 5, 3 * bs, 1, maxb * bs, 700, 2 * bs + 1, 0, 0],
                      np.int32)
    tables = np.where(np.arange(maxb)[None] * bs < lens[:, None], perm, nb)
    slots = np.full((b, 1), nb * bs, np.int32)
    for r in range(b):
        if lens[r]:
            p = int(lens[r]) - 1
            slots[r, 0] = int(tables[r, p // bs]) * bs + p % bs
    q, kn, vn = (_randn(rng, (b, 1, h, d), jnp.bfloat16) for _ in range(3))
    if quant:
        kb, vb = (jnp.asarray(rng.randint(-127, 128, (nb, bs, h * d)),
                              jnp.int8) for _ in range(2))
        scales = [jnp.asarray(rng.rand(nb, h) * 0.05 + 0.01, jnp.float32)
                  for _ in range(2)]
    else:
        kb, vb = (_randn(rng, (nb, bs, h * d), jnp.bfloat16)
                  for _ in range(2))
        scales = []
    tables, slots, lens_j = (jnp.asarray(x) for x in (tables, slots, lens))
    pos0 = jnp.maximum(lens_j - 1, 0)

    def call(q, kn, vn, kb, vb, *sc):
        kw = dict(k_scales=sc[0], v_scales=sc[1]) if sc else {}
        return rp.ragged_paged_attention_arrays(
            q, kn, vn, kb, vb, tables, pos0, lens_j, slots, **kw)

    name = f"ragged_{'int8' if quant else 'fp'}_{wname}"
    if AOT:
        _compile_only(call, [q, kn, vn, kb, vb, *scales])
        print(f"OK {name}", flush=True)
        return
    got, want = _kernel_and_fallback(call, (q, kn, vn, kb, vb, *scales))
    live = np.asarray(lens) > 0
    out, out_ref = (np.asarray(x[0], np.float32)[live] for x in (got, want))
    assert np.isfinite(out).all(), name
    np.testing.assert_allclose(out, out_ref, rtol=5e-2, atol=5e-2,
                               err_msg=name)
    for i, (g, w) in enumerate(zip(got[1:], want[1:])):
        # the write itself: fp pools and the int8 scales bitwise; int8
        # codes within one step (Mosaic and XLA may round x/scale apart)
        step = 1.0 if quant and i < 2 else 0.0
        diff = np.abs(np.asarray(g, np.float32) - np.asarray(w, np.float32))
        assert diff.max() <= step, \
            f"{name}: state {i} off by {diff.max()} (allowed {step})"
    print(f"OK {name}", flush=True)


def _kernel_and_fallback(call, args):
    """`call(*args)` through the decode kernel, then through its XLA
    fallback (PTPU_RAGGED_KERNEL=0).  The gate reads the variable while
    it is traced, and `jit` keeps a trace per FUNCTION: each side gets a
    function of its own, or the second would be the first's program."""
    import jax

    got = jax.jit(lambda *a: call(*a))(*args)
    os.environ["PTPU_RAGGED_KERNEL"] = "0"
    try:
        want = jax.jit(lambda *a: call(*a))(*args)
    finally:
        del os.environ["PTPU_RAGGED_KERNEL"]
    return got, want


def _ragged_vs_fallback(name, call, args, live):
    """`call(*args)` through the decode kernel against the same call
    through its XLA fallback: the `live` rows' outputs row by row, the
    pools bit for bit."""
    got, want = _kernel_and_fallback(call, args)
    out, out_ref = (np.asarray(x[0], np.float32)[live] for x in (got, want))
    assert np.isfinite(out).all(), name
    # over thousands of keys the output is a small mean of values: judge
    # it by its own size, row by row (bf16 operands: under 2%)
    rel = np.sqrt(((out - out_ref) ** 2).mean((1, 2, 3))
                  / (out_ref ** 2).mean((1, 2, 3)))
    assert rel.max() < 2e-2, f"{name}: relative error a row {rel}"
    for g, w in zip(got[1:], want[1:]):
        assert (np.asarray(g, np.float32) == np.asarray(w, np.float32)
                ).all(), f"{name}: pools differ"
    print(f"OK {name} (relative rms error a row, worst {rel.max():.4f})",
          flush=True)


def check_ragged_grouped(window):
    """The decode kernel with 48 query heads over pools of 8 K/V heads,
    with and without the window, against its XLA fallback: lengths 1, and
    4095, 4096, 4097 either side of the window, 8448, and a padding row;
    table entries wholly behind a window point nowhere, as the window
    group leaves them."""
    import jax.numpy as jnp
    from paddle_tpu.ops import ragged_paged_attention as rp

    hq, hkv, d, bs = (AFMOE[k] for k in ("hq", "hkv", "d", "bs"))
    rng = np.random.RandomState(11)
    lens = np.asarray([1, 4095, 4096, 4097, 8448, 0], np.int32)
    b, maxb = len(lens), 8448 // bs
    nb = b * maxb
    perm = rng.permutation(nb).astype(np.int32).reshape(b, maxb)
    blk = np.arange(maxb)[None] * bs
    live = blk < lens[:, None]
    if window:
        live &= blk + bs > lens[:, None] - window
    tables = np.where(live, perm, nb)
    slots = np.full((b, 1), nb * bs, np.int32)
    for r in range(b):
        if lens[r]:
            p = int(lens[r]) - 1
            slots[r, 0] = int(tables[r, p // bs]) * bs + p % bs
    q = _randn(rng, (b, 1, hq, d), jnp.bfloat16)
    kn, vn = (_randn(rng, (b, 1, hkv, d), jnp.bfloat16) for _ in range(2))
    kb, vb = (_randn(rng, (nb, bs, hkv * d), jnp.bfloat16)
              for _ in range(2))
    tables, slots, lens_j = (jnp.asarray(x) for x in (tables, slots, lens))
    pos0 = jnp.maximum(lens_j - 1, 0)

    def call(q, kn, vn, kb, vb):
        return rp.ragged_paged_attention_arrays(
            q, kn, vn, kb, vb, tables, pos0, lens_j, slots,
            **({"window": window} if window else {}))

    name = f"ragged_grouped_48over8_window{window}"
    if AOT:
        _compile_only(call, [q, kn, vn, kb, vb])
        print(f"OK {name}", flush=True)
        return
    _ragged_vs_fallback(name, call, (q, kn, vn, kb, vb), lens > 0)


# the decode kernel's calls in the serving cells (BENCHMARK.json): rows,
# query heads over K/V heads of 128, block size, pool blocks, table width,
# window, live tokens a row (chat: mean 448; docqa: mean 1120; afmoe:
# prompts of 1k / 4k / 8k at 13:13:6 rows, part-way through an answer)
_SPREAD16 = [448 + round((r - 7.5) * 37) for r in range(16)]
_SPREAD8 = [1120 + round((r - 3.5) * 100) for r in range(8)]
_LONGMIX = [1100] * 13 + [4200] * 13 + [8300] * 6
# agents-c64's rows by the time they stay: prompts 256 / 1024 / 4096 with
# about half their answers decoded
_AGENTS = [400] * 26 + [1200] * 29 + [4200] * 9
CELLS = {
    "chat": dict(hq=16, hkv=16, bs=16, nb=2048, maxb=128, window=None,
                 lens=_SPREAD16),
    "docqa": dict(hq=32, hkv=32, bs=16, nb=1024, maxb=128, window=None,
                  lens=_SPREAD8),
    "afmoe_window": dict(hq=48, hkv=8, bs=64, nb=2080, maxb=132, window=4096,
                         lens=_LONGMIX),
    "afmoe_full": dict(hq=48, hkv=8, bs=64, nb=4224, maxb=132, window=None,
                       lens=_LONGMIX),
    "lfm2": dict(hq=32, hkv=8, d=64, bs=64, nb=4608, maxb=72, window=None,
                 lens=_AGENTS),
}


# longctx-c64's rows by the time they stay: prompts 4096 / 8192 / 16384 at
# 4:4:2 with about half their answers decoded (541k live rows of 786k)
_LONGCTX = [4300] * 26 + [8450] * 26 + [16900] * 12
LATENT_CELL = dict(h=32, dk=320, dv=256, bs=64, nb=12288, maxb=272,
                   lens=_LONGCTX)


def check_latent_cell():
    """The latent decode kernel at `mistral-small-4-ep8-l8.longctx-c64`'s
    call, alone: against its XLA fallback once (on 8 of the rows: the
    fallback gathers every row's whole table), then timed as the engine
    runs it - 8 calls in one program, the pool handed from one to the next
    in place.  `--split`: again with its products cut out (DMA only), with
    its DMAs cut out (math only), and over a row of TWO lane tiles (a key
    of 256 in a pool of 256 lanes: the `c_kv` half of a split layout with
    nothing done for `k^rope`, the least a split layout could cost)."""
    import contextlib
    import time
    from unittest import mock

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ragged_paged_attention as rp

    c = LATENT_CELL
    h, dv, bs, nb, maxb = (c[k] for k in ("h", "dv", "bs", "nb", "maxb"))
    calls, scale = 8, 128 ** -0.5 * (0.1 * np.log(128.0) + 1) ** 2
    rng = np.random.RandomState(19)

    def inputs(lens):
        lens = np.asarray(lens, np.int32)
        b = len(lens)
        live = np.arange(maxb)[None] * bs < lens[:, None]
        tables = np.full((b, maxb), nb, np.int32)
        tables[live] = rng.permutation(nb)[:live.sum()]
        p = lens - 1
        slots = (tables[np.arange(b), p // bs] * bs + p % bs)[:, None]
        return tuple(jnp.asarray(x) for x in (tables, p, lens, slots))

    def pool(dk):       # made on the device, zeros past the key's lanes
        rows = jax.random.normal(jax.random.PRNGKey(5), (nb, bs, dk),
                                 jnp.bfloat16)
        return jnp.pad(rows, ((0, 0), (0, 0), (0, -dk % 128)))

    def rows_of(b, dk):
        return (_randn(rng, (b, 1, h, dk), jnp.bfloat16),
                _randn(rng, (b, 1, dk), jnp.bfloat16))

    def one(q, new, pl_, idx, i=0):
        return rp.ragged_latent_attention_arrays(
            q + jnp.asarray(i, q.dtype), new, pl_, *idx, value_dim=dv,
            scale=scale)

    name = "cell_mistral4_latent"
    idx = inputs(c["lens"])

    def program(q, new):
        def run(pl_):
            acc = 0.0
            for i in range(calls):
                o, pl_ = one(q, new, pl_, idx, i)
                acc += o.astype(jnp.float32)
            return acc, pl_
        return jax.jit(run, donate_argnums=(0,))

    if AOT:
        _compile_only(program(*rows_of(len(c["lens"]), c["dk"])),
                      [pool(c["dk"])])
        print(f"OK {name}", flush=True)
        return
    # the new rows and their slots are CLOSED OVER, constants of the
    # program: what the TPU compiler folded to zeros in the fallback's
    # writer until `paged_cache_update_arrays` kept the offsets behind a
    # barrier (my chip runs 2-3, PR 34; PERF.md 6)
    few = c["lens"][::8]
    few_idx = inputs(few)
    few_q, few_new = rows_of(len(few), c["dk"])
    _ragged_vs_fallback(
        name, lambda q_, pl_: one(q_, few_new, pl_, few_idx),
        (few_q, pool(c["dk"])), np.asarray(few) > 0)
    tokens = int(np.sum(c["lens"]))
    tiles = int(np.sum(-(-(np.asarray(c["lens"]) - 1) // 256)))

    def timed(what, dk=c["dk"]):
        lanes = dk + -dk % 128
        with contextlib.ExitStack() as cut:
            for obj, attr, value in _cut(rp, what):
                cut.enter_context(mock.patch.object(obj, attr, value,
                                                    create=True))
            jax.clear_caches()      # the kernel call is traced once a shape
            fn = program(*rows_of(len(c["lens"]), dk))
            state = jax.block_until_ready(fn(pool(dk)))[1]
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            state = fn(state)[1]
        jax.block_until_ready(state)
        per = (time.perf_counter() - t0) / (reps * calls)
        print(f"CELL mistral4_latent {what} key {dk} in {lanes} lanes: "
              f"{per * 1e3:.4f} ms a call, {per * 1e6 / tiles:.3f} us a "
              f"tile ({tiles} tiles of 256 rows, {len(c['lens'])} rows, "
              f"{tokens} live rows), {tokens * 640 / per / 1e9:.1f} GB/s of "
              f"latents at the published 640 B a row "
              f"({tokens * lanes * 2 / per / 1e9:.1f} GB/s moved at the "
              f"pool's {lanes * 2})", flush=True)
        jax.clear_caches()          # no later trace may meet a cut kernel

    timed("as_is")
    if SPLIT:
        timed("dma_only")
        timed("math_only")
        timed("as_is", dk=256)


class _NoCopy:
    """A DMA descriptor that moves nothing (`--split`, math only)."""

    def start(self, *a, **k):
        pass

    wait = start


def _cut(rp, what):
    """Patches, as (object, attribute, value), that take one half of the
    decode kernel out of its trace: `dma_only` makes every matrix product
    of the stream a constant (the blocks still arrive, nothing reads
    them but the one elementwise pass the segment body makes over V),
    `math_only` makes every DMA a no-op (the products run over whatever
    the buffers hold), `segment_body` sends a call that would take the
    per-head products through the segment-indicator body."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    if what == "dma_only":
        def dot0(a, b, transpose_b=False):
            return jnp.zeros((a.shape[0], b.shape[0 if transpose_b else 1]),
                             jnp.float32)

        helpers = rp._decode_seg_helpers

        def helpers0(h, d, fast):
            seg, expand, _ = helpers(h, d, fast)
            return seg, expand, lambda a3, mat, exact=False: jnp.zeros(
                a3.shape[:2] + (mat.shape[1],), jnp.float32)

        return [(rp, "_dot_f32", dot0), (rp, "_decode_seg_helpers", helpers0)]
    if what == "math_only":
        return [(pltpu, "make_async_copy", lambda *a, **k: _NoCopy())]
    if what == "segment_body":
        return [(rp, "_head_products_ok", lambda *a, **k: False)]
    return []


def check_ragged_cell(cell):
    """One serving cell's decode-kernel call, alone: against the XLA
    fallback once, then timed as the engine runs it - 24 of them in
    one program, the pools handed from one to the next in place."""
    import contextlib
    import time
    from unittest import mock

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import ragged_paged_attention as rp

    c = CELLS[cell]
    hq, hkv, bs, nb, maxb, window = (c[k] for k in (
        "hq", "hkv", "bs", "nb", "maxb", "window"))
    d, calls = c.get("d", 128), 24    # a 24-layer program's worth, back to back
    rng = np.random.RandomState(17)

    def inputs(lens):
        lens = np.asarray(lens, np.int32)
        b = len(lens)
        kb = np.arange(maxb)[None] * bs
        live = kb < lens[:, None]
        if window:
            live &= kb + bs > lens[:, None] - window
        tables = np.full((b, maxb), -1, np.int32)
        tables[live] = rng.permutation(nb)[:live.sum()]
        p = lens - 1
        slots = (tables[np.arange(b), p // bs] * bs + p % bs)[:, None]
        seen = np.minimum(lens, window) if window else lens
        return (tuple(jnp.asarray(x) for x in (tables, p, lens, slots)),
                int(live.sum()), int(seen.sum()))

    q = _randn(rng, (len(c["lens"]), 1, hq, d), jnp.bfloat16)
    kn, vn = (_randn(rng, (len(c["lens"]), 1, hkv, d), jnp.bfloat16)
              for _ in range(2))
    kw = {"window": window} if window else {}

    def pools():        # made on the device
        return tuple(jax.random.normal(k, (nb, bs, hkv * d), jnp.bfloat16)
                     for k in jax.random.split(jax.random.PRNGKey(5)))

    def program(idx):
        def run(kb, vb):
            acc = jnp.zeros(q.shape, jnp.float32)
            for i in range(calls):
                o, kb, vb = rp.ragged_paged_attention_arrays(
                    q + jnp.asarray(i, q.dtype), kn, vn, kb, vb, *idx, **kw)
                acc += o.astype(jnp.float32)
            return acc, kb, vb
        return jax.jit(run, donate_argnums=(0, 1))

    idx, blocks, tokens = inputs(c["lens"])
    name = f"cell_{cell}"
    if AOT:
        _compile_only(lambda kb, vb: program(idx)(kb, vb), list(pools()))
        print(f"OK {name}", flush=True)
        return
    _ragged_vs_fallback(
        name, lambda kb, vb: rp.ragged_paged_attention_arrays(
            q, kn, vn, kb, vb, *idx, **kw), pools(),
        np.asarray(c["lens"]) > 0)

    def timed(what, lens=None):
        i, n_blocks, n_tokens = inputs(lens) if lens else (idx, blocks,
                                                           tokens)
        with contextlib.ExitStack() as cut:
            for obj, attr, value in _cut(rp, what):
                cut.enter_context(mock.patch.object(obj, attr, value,
                                                    create=True))
            jax.clear_caches()      # the kernel call is traced once a shape
            fn = program(i)
            state = jax.block_until_ready(fn(*pools()))[1:]
        reps = 5
        t0 = time.perf_counter()
        for _ in range(reps):
            state = fn(*state)[1:]
        jax.block_until_ready(state)
        call = (time.perf_counter() - t0) / (reps * calls)
        kv_bytes = n_tokens * hkv * d * 2 * 2
        print(f"CELL {cell} {what + (' one-token rows' if lens else '')}: "
              f"{call * 1e3:.4f} ms a call, {call * 1e6 / n_blocks:.3f} us a "
              f"block ({n_blocks} blocks of {bs}, {len(i[2])} rows), "
              f"{kv_bytes / call / 1e9:.1f} GB/s of live K/V", flush=True)
        jax.clear_caches()          # no later trace may meet a cut kernel

    timed("as_is")
    if SPLIT:
        timed("dma_only")
        timed("math_only")
        timed("as_is", lens=[1] * len(c["lens"]))
        if hasattr(rp, "_head_products_ok") and bs < 64:
            timed("segment_body")


def check_flash_grouped(window, s=8192):
    """Flash forward over 48 query heads and 8 K/V heads at S = 8192, with
    and without the window, against plain XLA attention on the first and
    the last 256 query rows (all of it would be a 13 GB score matrix)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_ops as po

    hq, hkv, d = (AFMOE[k] for k in ("hq", "hkv", "d"))
    rng = np.random.RandomState(13)
    q = _randn(rng, (1, s, hq, d), jnp.bfloat16)
    k, v = (_randn(rng, (1, s, hkv, d), jnp.bfloat16) for _ in range(2))

    def fwd(q, k, v):
        o = po.flash_attention_arrays(q, k, v, is_causal=True,
                                      window=window)
        return o[:, :256], o[:, -256:]

    def ref(q, k, v):
        outs = []
        for lo in (0, s - 256):
            i = lo + jnp.arange(256)[:, None]
            j = jnp.arange(s)[None]
            seen = j <= i
            if window:
                seen &= i - j < window
            qq = q[:, lo:lo + 256].reshape(1, 256, hkv, hq // hkv, d)
            sc = jnp.einsum("bqhgd,bkhd->bhgqk", qq, k,
                            preferred_element_type=jnp.float32) / d ** 0.5
            p = jax.nn.softmax(jnp.where(seen, sc, -1e30), axis=-1)
            outs.append(jnp.einsum("bhgqk,bkhd->bqhgd", p.astype(v.dtype),
                                   v).reshape(1, 256, hq, d))
        return tuple(outs)

    _check(f"flash_grouped_48over8_window{window}_s{s}", fwd, ref, [q, k, v])


def check_afmoe_engine():
    """`trinity-large-ep8-l5` through LLMEngine at its published widths and
    the cell's engine settings: prompts of 1,024 and 4,224 tokens (one
    past the window) decoded together for 48 steps and one of 8,192 for 8,
    the served tokens against the plain reference's full forward, then
    against the reference's deliberate faults; and, without a cache, every
    logit of one 1,024-token sequence."""
    import json
    import time

    import jax
    import jax.numpy as jnp
    from benchmark.lib import family, reference_afmoe as ref
    from paddle_tpu.serving import EngineConfig, LLMEngine
    from paddle_tpu.serving.scheduler import SamplingParams

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-large-ep8-l5.json")) as f:
        config = json.load(f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "longmix-c32.json")) as f:
        engine_kw = json.load(f)["engine"]
    model, cfg = family.build_model(config, 2 ** 31 + 5)
    model.eval()
    params = ref.params_from_model(model)
    rng = np.random.RandomState(5)

    # every logit, no cache: the error of a position is rounding, or a
    # token the router sent elsewhere than the float32 reference did
    ids = rng.randint(0, cfg.vocab_size, 1024)
    got = np.asarray(jax.jit(model.forward_arrays)(
        model.param_arrays(), jnp.asarray(ids[None])), np.float32)[0]
    want = np.asarray(ref.logits(params, jnp.asarray(ids), config))
    err = np.sqrt(((got - want) ** 2).mean(-1))
    print("afmoe forward: rms logit error a position, quantiles "
          "50/90/99/100%: "
          f"{np.round(np.percentile(err, [50, 90, 99, 100]), 4).tolist()} "
          f"(logit std {want.std(-1).mean():.3f})", flush=True)
    del got, want

    engine = LLMEngine(model, EngineConfig(**engine_kw))
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (1024, 4224, 8192)]
    t0 = time.perf_counter()
    outs = engine.generate(prompts[:2], SamplingParams(max_new_tokens=48))
    outs += engine.generate(prompts[2:], SamplingParams(max_new_tokens=8))
    print(f"afmoe engine: 3 prefills, 48 + 8 decode steps in "
          f"{time.perf_counter() - t0:.1f} s (compiles included); window "
          f"group released {engine.caches['window'].released} blocks; "
          f"peak {jax.devices()[0].memory_stats()['peak_bytes_in_use']:,} "
          "bytes", flush=True)
    for cache in engine.caches.values():
        cache.k_blocks = cache.v_blocks = None
    del engine
    readings = {}
    for fault in ref.FAULTS:
        if fault == "fp8":
            readings["8k"] = _served_margins(ref, params, config, prompts[2:],
                                             outs[2:], None)
        readings[fault or "none"] = _served_margins(
            ref, params, config, prompts[:2], outs[:2], fault)
    for name, r in readings.items():
        print(f"afmoe engine: served tokens under the reference with fault "
              f"{name}: {json.dumps(r)}", flush=True)
    assert readings["none"]["median"] <= 0.05, readings["none"]
    print("OK afmoe_engine", flush=True)


def _served_margins(ref, params, config, prompts, outs, fault):
    """How far each served token's logit lies under its position's
    largest in the reference (computed with `fault`): max, p90, median,
    and the share of tokens within 0.25."""
    all_m = []
    for prompt, out in zip(prompts, outs):
        width = -(-len(out) // 128) * 128
        ids = np.zeros((1, width), np.int32)
        ids[0, :len(out)] = out
        margins, _ = ref.greedy_margins(params, ids, config, fault=fault)
        all_m.append(margins[0, len(prompt) - 1:len(out) - 1])
    m = np.concatenate(all_m)
    return {"tokens": int(m.size), "max": round(float(m.max()), 4),
            "p90": round(float(np.percentile(m, 90)), 4),
            "median": round(float(np.median(m)), 4),
            "within_0.25": round(float((m <= 0.25).mean()), 4)}


# (rows, tokens a row, first position): a whole prompt, a chunk from the
# middle of a block, a speculative verify, one decode token a row
WRITES = {"prompt384": (1, 384, 0), "chunk256+5": (1, 256, 1029),
          "verify16x5": (16, 5, 200), "decode16x1": (16, 1, 333)}


def check_ties(sequences=4, length=12288, seed=3300000029):
    """See the module docstring, `--ties`."""
    import json
    import time

    import jax
    import jax.numpy as jnp

    from benchmark.lib import family
    from benchmark.lib import reference_mistral4 as ref

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "mistral-small-4-ep8-l8.json")) as f:
        config = json.load(f)
    t0 = time.perf_counter()
    model, cfg = family.build_model(config, seed)
    model.eval()
    params = ref.params_from_model(model)
    held = model.param_arrays()
    program = jax.jit(lambda p, ids: jnp.argmax(
        model.forward_arrays(p, ids)[0].astype(jnp.float32), -1))
    rng = np.random.default_rng(5)
    read = {}               # fault -> ([main path alone], [least of branches])
    for i in range(sequences):
        ids = rng.integers(0, cfg.vocab_size, length).astype(np.int32)
        pick = program(held, jnp.asarray(ids)[None])
        for fault in ref.FAULTS if i == 0 else (None,):
            for tie, into in zip((0.0, None), read.setdefault(fault,
                                                              ([], []))):
                into.append(np.asarray(ref.choice_margins(
                    params, ids, pick, config, fault=fault, tie=tie)[0]))
        print(f"TIES sequence {i} of {length} positions done at "
              f"{time.perf_counter() - t0:.0f} s", flush=True)
    for fault, pair in read.items():
        for what, parts in zip(("main path alone", "least of branches"),
                               pair):
            m = np.concatenate(parts)
            print(f"TIES {fault or 'sound'!s:>15} {what:>17}: {m.size} "
                  f"positions, share within 0.25 {np.mean(m <= 0.25):.4f}, "
                  f"over 0.5 / 1 / 1.5 / 2: {int((m > 0.5).sum())} / "
                  f"{int((m > 1).sum())} / {int((m > 1.5).sum())} / "
                  f"{int((m > 2).sum())}, max {m.max():.3f}", flush=True)
    print(f"TIES a tie is a gap of at most {ref.TIE} of the score; a layer "
          f"branches at most 1/{ref.SPAWN} of the positions", flush=True)
    row = rng.integers(0, cfg.vocab_size, (1, 17408)).astype(np.int32)
    for what in ("first", "second"):
        t1 = time.perf_counter()
        ref.greedy_margins(params, row, config)
        print(f"TIES greedy_margins, one row of 17,408 positions, {what} "
              f"call: {time.perf_counter() - t1:.1f} s", flush=True)
    print("OK check_ties", flush=True)


def check_writes(h=16, d=128, layers=12):
    """The KV pool writers at the 1.3B pool (bf16 at block 16, int8 at
    block 32): the pool after `paged_cache_update_arrays` /
    `quantized_cache_update_arrays` against a numpy scatter of the same
    rows, every slot not named bit for bit as it was; then what one
    write costs, and what one layer of the C > 1 XLA fallback costs
    (write + gather + attention, `ragged_paged_attention_arrays`), as
    the engine's programs run them: `layers` of them unrolled in one
    program, each on donated pools of its own.  A tree from
    before PR 27 (`--tree`) keeps its pools ``[nb, bs, H, D]`` and its
    layout paragraph says so: it gets them in that shape."""
    import time

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.ops import ragged_paged_attention as rp

    flat = "num_heads * head_dim]" in pa.__doc__

    def timed(step, make, rows):
        if AOT:
            return _compile_only(step, [make(), rows])
        fn = jax.jit(lambda sts, r: [step(st, r + jnp.asarray(i, r.dtype))
                                     for i, st in enumerate(sts)],
                     donate_argnums=(0,))
        sts = jax.block_until_ready(fn([make() for _ in range(layers)], rows))
        t0 = time.perf_counter()
        for _ in range(5):
            sts = fn(sts, rows)
        jax.block_until_ready(sts)
        return (time.perf_counter() - t0) / (5 * layers) * 1e6

    for quant in (False, True):
        bs = 32 if quant else 16
        nb = 2048 * 16 // bs
        rng = np.random.RandomState(7)
        perm = rng.permutation(nb).astype(np.int32)
        shape = (nb, bs, h * d) if flat else (nb, bs, h, d)
        for case, (b, s, start) in WRITES.items():
            maxb = 2048 // bs           # a 2048-token table a row
            pos = start + np.arange(s)
            tables = perm[:b * maxb].reshape(b, maxb)
            slots = (tables[:, pos // bs] * bs + pos % bs).astype(np.int32)
            rows = _randn(rng, (b, s, h, d), jnp.bfloat16)
            name = f"write_{'int8' if quant else 'fp'}_{case}"
            def fresh():          # made on the device, 134 MB each
                keys = jax.random.split(jax.random.PRNGKey(11))
                if not quant:
                    return tuple(jax.random.normal(k, shape, jnp.bfloat16)
                                 for k in keys)
                return tuple(jax.random.randint(k, shape, -127, 128, jnp.int8)
                             for k in keys) + tuple(
                    jnp.full((nb, h), 0.05, jnp.float32) for _ in keys)

            def write(st, r):
                if quant:
                    return tuple(pa.quantized_cache_update_arrays(
                        st[0], st[1], r, slots))
                return (pa.paged_cache_update_arrays(st[0], r, slots),)

            def layer(st, r):     # st: the outputs' sum, then the pools
                kw = dict(k_scales=st[3], v_scales=st[4]) if quant else {}
                out = rp.ragged_paged_attention_arrays(
                    r, r, r, st[1], st[2], jnp.asarray(tables),
                    jnp.full((b,), start, jnp.int32),
                    jnp.full((b,), start + s, jnp.int32), slots, **kw)
                return (st[0] + out[0].astype(jnp.float32), *out[1:])

            state = fresh()[::2]          # (K pool[, its scales])
            if not AOT:
                before = np.asarray(state[0]).reshape(nb * bs, h * d)
                once = jax.jit(write)(state, rows)
                after = np.asarray(once[0]).reshape(nb * bs, h * d)
                named = slots.reshape(-1)
                rest = np.ones(nb * bs, bool)
                rest[named] = False
                want, step = np.asarray(rows, np.float32), 0.0
                if quant:
                    # the rows' amax / 127 stays under the 0.05 every
                    # block has: no scale grows, no old code is rescaled;
                    # the device may round x / scale one step apart
                    np.testing.assert_array_equal(np.asarray(once[1]),
                                                  np.asarray(state[1]))
                    want, step = np.round(want / 0.05), 1.0
                np.testing.assert_array_equal(after[rest], before[rest], name)
                diff = np.abs(after[named].astype(np.float32)
                              - want.reshape(-1, h * d))
                assert diff.max() <= step, f"{name}: {diff.max()}"
            took = {"write": timed(write, lambda: fresh()[::2], rows)}
            if s > 1:           # at C = 1 the kernel runs, not the fallback
                took["fallback layer"] = timed(
                    layer, lambda: (jnp.zeros(rows.shape, jnp.float32),
                                    *fresh()), rows)
            print(f"OK {name}" + "".join(
                f"  {k} {v:.1f} us" for k, v in took.items() if v), flush=True)


SAMPLER_SHAPES = {"lfm2": (64, 65536), "chat": (16, 50304),
                  "afmoe": (32, 25024), "docqa": (8, 50304)}
# every row of the batch: (do_sample, temperature, top_k, top_p)
SAMPLER_BATCHES = {"greedy": (False, 1.0, 0, 1.0),
                   "temperature": (True, 0.8, 0, 1.0),
                   "top_k50_top_p0.9": (True, 0.8, 50, 0.9)}


def _module_us(fn, args, reps=10):
    """Mean device time of `fn(*args)`, us, from the `XLA Modules` line
    of a profiler trace over `reps` runs."""
    import glob
    import tempfile

    import jax
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
        finally:
            jax.profiler.stop_trace()
        [path] = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                        "*.xplane.pb"))
        took = [ev.duration_ns
                for plane in ProfileData.from_file(path).planes
                if plane.name.startswith("/device:TPU:0")
                for line in plane.lines if line.name == "XLA Modules"
                for ev in line.events]
    assert len(took) == reps, f"{len(took)} module events for {reps} runs"
    return sum(took) / reps / 1e3


def check_sampler(cell):
    """The sample program at one cell's logits against the two-sort
    program it replaced: equal tokens and keys, then what each costs."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.engine import _sample_program
    from tests.test_sampler import two_sort_row

    b, v = SAMPLER_SHAPES[cell]
    programs = {"change": jax.jit(_sample_program),
                "two_sorts": jax.jit(jax.vmap(two_sort_row))}
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(33), (b, v),
                                     jnp.float32)
    keys = jnp.asarray(np.stack([np.asarray(jax.random.PRNGKey(i), np.uint32)
                                 for i in range(b)]))
    for batch, (ds, t, k, p) in SAMPLER_BATCHES.items():
        args = (logits, keys, jnp.full((b,), ds), jnp.full((b,), t, jnp.float32),
                jnp.full((b,), k, jnp.int32), jnp.full((b,), p, jnp.float32))
        if AOT:
            _compile_only(_sample_program, args)
            continue
        got, want = (jax.device_get(fn(*args)) for fn in programs.values())
        for g, w in zip(got, want):
            assert (g == w).all(), f"sampler_{cell} {batch}: {g} != {w}"
        print(f"SAMPLER {cell} [{b},{v}] {batch}: tokens and keys equal; "
              + ", ".join(f"{name} {_module_us(fn, args):.1f} us"
                          for name, fn in programs.items()), flush=True)
    print(f"OK sampler_{cell}", flush=True)


def check_retention(rows=24, hq=40, hkv=8, d=128, prompt=1024):
    """Both retention kernels at the Brumby cell's calls against the XLA
    form, then what a call of each costs."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import power_retention as pr

    rng = np.random.RandomState(0)
    bf = jnp.bfloat16
    shape = pr.state_shape(hkv, d)

    def unit(x):        # a head as the per-head RMSNorm leaves it
        return (x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True))).astype(bf)

    def inputs(b, t):
        q, k = (unit(_randn(rng, (b, t, h, d), jnp.float32))
                for h in (hq, hkv))
        v = _randn(rng, (b, t, hkv, d), bf)
        log_g = jnp.log(jnp.asarray(rng.uniform(0.2, 0.999, (b, t, hkv)),
                                    jnp.float32))
        return q, k, v, log_g

    slots = jnp.asarray(rng.permutation(rows), jnp.int32)
    one = slots[:1]

    def prefill_kernel(fresh):
        return lambda *a: pr.retention_prefill(*a, one, fresh)

    def prefill_xla(fresh):
        def ref(q, k, v, log_g, pool):
            o, new = pr.retention_chunked(
                q, k, v, log_g, None if fresh else pool[one], chunk=64)
            return o.astype(bf), pool.at[one].set(new)
        return ref

    def decode_kernel(q, k, v, log_g, pool, fast=True):
        return pr.retention_decode(q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
                                   pool, slots, fast=fast)

    def decode_xla(q, k, v, log_g, pool):
        o, new = pr._decode_math(q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
                                 pool[slots])
        return o, pool.at[slots].set(new)

    if AOT:
        pool = np.zeros((rows + 1,) + shape, np.float32)
        for fresh in (True, False):
            _compile_only(prefill_kernel(fresh),
                          inputs(1, prompt) + (pool,))
        _compile_only(decode_kernel, inputs(rows, 1) + (pool,))
        print("OK retention kernels compile", flush=True)
        return
    pool = jnp.zeros((rows + 1,) + shape, jnp.float32)
    first, second = inputs(1, prompt), inputs(1, prompt)
    with jax.default_matmul_precision("highest"):
        # the kernel's MXU operands are bfloat16: one output in millions,
        # where a denominator is small, strays past 5e-2
        _check("retention_prefill_fresh", prefill_kernel(True),
               prefill_xla(True), first + (pool,), tol=1e-1)
        _, pool = jax.jit(prefill_xla(True))(*first, pool)
        _check("retention_prefill_carried", prefill_kernel(False),
               prefill_xla(False), second + (pool,), tol=1e-1)
        # every slot a state of its own, as far as a prefill leaves one
        _, seeded = jax.jit(prefill_xla(False))(*second, pool)
        pool = jnp.broadcast_to(seeded[one], (rows + 1,) + shape) \
            * jnp.linspace(0.5, 1.5, rows + 1)[:, None, None, None, None]
        step = inputs(rows, 1)
        _check("retention_decode", decode_kernel, decode_xla,
               step + (pool,), tol=2e-2)
    def ms_a_call(fn, args, pool, reps=10):
        """Wall time of `fn(*args, pool) -> (_, pool)`, the pool donated
        and handed on as in the engine (a call that keeps its pool pays
        XLA's copy of all of it first: 0.9 GB here)."""
        fn = jax.jit(fn, donate_argnums=(len(args),))
        _, pool = fn(*args, pool)
        jax.block_until_ready(pool)
        t0 = time.perf_counter()
        for _ in range(reps):
            out, pool = fn(*args, pool)
        jax.block_until_ready((out, pool))
        return (time.perf_counter() - t0) / reps * 1e3, pool

    err = float(jnp.abs(jax.jit(decode_kernel)(*step, pool)[0]
                        - jax.jit(decode_xla)(*step, pool)[0]).max())
    print(f"retention_decode: largest error of an output {err:.5f}")
    ms, pool = ms_a_call(prefill_kernel(True), first, pool)
    flop = 2 * pr.published_state_numbers(hkv, d) * (hq + hkv) / hkv * prompt
    print(f"retention_prefill {prompt} positions: {ms:.3f} ms a call "
          f"({flop / ms / 1e9:.1f} TFLOP/s through the published state)")
    moved = 2 * rows * pr.published_state_numbers(hkv, d) * 4
    for fast, what in ((True, "3 bfloat16 products"),
                       (False, "float32 at the highest precision")):
        fn = functools.partial(decode_kernel, fast=fast)
        ms, pool = ms_a_call(fn, step, pool)
        print(f"retention_decode {rows} rows, readout in {what}: {ms:.3f} "
              f"ms a call ({moved / ms / 1e6:.1f} GB/s of published state)",
              flush=True)


def check_generate():
    """`generate()` with the flash-decode kernel forced on: the one
    integration check of the kernel-inside-generate routing."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import GPTForCausalLM, gpt_test_config

    # S_max=256 is below the auto policy's threshold
    os.environ["PTPU_FLASH_DECODE"] = "1"
    cfg = gpt_test_config(stacked_blocks=True, sequence_parallel=False,
                          max_position_embeddings=256)
    paddle.seed(0)
    model = GPTForCausalLM(cfg)
    model.bfloat16()
    model.eval()
    rng = np.random.RandomState(0)
    ids = Tensor(jnp.asarray(rng.randint(0, cfg.vocab_size, (2, 120)),
                             jnp.int32))
    out = model.generate(ids, max_new_tokens=8)
    assert tuple(out.shape) == (2, 128)
    print("OK generate", flush=True)


def main():
    global _AOT_SHARDING
    import jax

    if AOT:
        from jax.experimental import topologies
        from jax.sharding import Mesh, NamedSharding, PartitionSpec
        from paddle_tpu.ops import pallas_ops, ragged_paged_attention

        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        _AOT_SHARDING = NamedSharding(
            Mesh(np.array(topo.devices[:1]), ("x",)), PartitionSpec())
        # the gates ask the attached platform; the target here is the
        # topology, so answer for it
        pallas_ops._on_tpu = ragged_paged_attention._on_tpu = lambda: True
    else:
        plat = jax.devices()[0].platform
        assert plat == "tpu", f"not on a TPU backend: {plat}"
        print(f"device: {jax.devices()[0].device_kind} x{len(jax.devices())}",
              flush=True)
    checks = [(f"{fn.__name__}_{wname}", fn, (wname, h, d) + extra)
              for wname, (h, d) in WIDTHS.items()
              for fn, extra in ((check_flash, ()), (check_flash_decode, ()),
                                (check_ragged, (False,)),
                                (check_ragged, (True,)))]
    checks += [(f"{fn.__name__}_{w}", fn, (w,))
               for fn in (check_ragged_grouped, check_flash_grouped)
               for w in (None, AFMOE["window"])]
    if AFMOE_ONLY:
        checks = [c for c in checks if "grouped" in c[0]]
        if not AOT:
            checks.append(("check_afmoe_engine", check_afmoe_engine, ()))
    if not AOT and not AFMOE_ONLY:
        checks.append(("check_generate", check_generate, ()))
    if not AFMOE_ONLY:
        checks.append(("check_retention", check_retention, ()))
    if not AFMOE_ONLY:
        checks.append(("check_writes", check_writes, ()))
    if WRITES_ONLY:
        checks = [c for c in checks if c[1] is check_writes]
    if CELLS_ONLY:
        checks = [(f"check_ragged_cell_{c}", check_ragged_cell, (c,))
                  for c in CELLS]
        checks.append(("check_latent_cell", check_latent_cell, ()))
    if LATENT_ONLY:
        checks = [("check_latent_cell", check_latent_cell, ())]
    if SAMPLER_ONLY:
        checks = [(f"check_sampler_{c}", check_sampler, (c,))
                  for c in SAMPLER_SHAPES]
    if TIES_ONLY:
        checks = [("check_ties", check_ties, ())]
    if RETENTION_ONLY:
        checks = [("check_retention", check_retention, ())]
    for name, fn, args in checks:
        with _Watchdog(name, 900.0 if fn in (check_writes, check_afmoe_engine,
                                             check_ragged_cell,
                                             check_latent_cell, check_ties,
                                             check_retention) else 240.0):
            fn(*args)
    print("ALL AOT COMPILES OK" if AOT else "ALL ONCHIP CHECKS OK")


if __name__ == "__main__":
    main()
