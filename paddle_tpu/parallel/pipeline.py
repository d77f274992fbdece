"""Pipeline parallelism over the 'pp' mesh axis.

Reference analog: fleet/meta_parallel/pipeline_parallel.py:31 (1F1B
micro-batch schedule) + pp_utils/p2p_communication.py:298 (send_v2/recv_v2
NCCL p2p with tensor-meta handshakes) + pp_layers.py:209 (LayerDesc
segmentation).

TPU-native re-design: there is no host-driven schedule and no p2p
handshake. The whole pipeline — every micro-batch, every stage hop — is ONE
compiled XLA program:

- stage weights live in stacked arrays with a leading stage dim sharded on
  'pp' (each device group holds only its stage's slice),
- the micro-batch rotation is a `lax.scan` whose carry hops stages via
  `lax.ppermute` over ICI (the collective-permute the reference emulates
  with NCCL send/recv),
- the schedule is GPipe-shaped (M + pp - 1 ticks); XLA's latency-hiding
  scheduler overlaps the permute DMA with the next tick's compute, which is
  what hand-written 1F1B overlap achieves in the reference,
- only 'pp' is manual (shard_map axis_names={'pp'}); dp/mp/sp/ep stay in
  GSPMD-auto mode so tensor-parallel constraints inside the stage body
  keep working.

Functions here are array-level (jnp in, jnp out); `apply`-wrapped use lives
in models (GPTStackedBlocks) and meta_parallel.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .mesh import get_mesh, axis_size
from .. import monitor
from ..profiler import RecordEvent

__all__ = ["pipeline_apply", "pipeline_1f1b", "scan_blocks"]


def scan_blocks(block_fn: Callable, stacked_params: Any, x,
                unroll: int | None = None, aux: Any = None):
    """Apply L stacked blocks sequentially via lax.scan (single-stage path;
    compile time O(1) in depth — the TPU answer to the reference's per-layer
    Program ops).

    aux: optional pytree of per-token metadata (e.g. packed-sequence
    segment ids) passed unchanged to every block as a third argument:
    block_fn(params_slice, h, aux). Constant across layers, so it rides
    the scan closure, not the carry.

    Default unroll policy (override with PTPU_SCAN_UNROLL=<n>, 0 = full):
    FULLY unroll when depth <= 32, else keep the rolled scan. Measured on
    v5e (GPT-2 124M, batch 8 x seq 1024): full unroll 108.3k tokens/sec vs
    92k rolled (+18%) — XLA schedules DMA prefetch and fusion across block
    boundaries that a scan body boundary forbids. PARTIAL unroll is a trap
    (unroll=2: 65k, unroll=4: 60k — worse than rolled) and is never chosen
    automatically. Deep stacks keep O(1)-in-depth compile time. Pipeline
    stage bodies pass an explicit unroll=1: they already sit inside the
    scanned pipeline tick loop, where replicating the stage body would
    multiply the pipeline program's size per tick (unmeasured, and the
    bench above only covers the single-stage path)."""

    def _depth():
        return jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

    if unroll is None:
        env = os.environ.get("PTPU_SCAN_UNROLL")
        unroll = int(env) if env is not None else (
            _depth() if _depth() <= 32 else 1)
    if unroll <= 0:
        unroll = _depth()

    if aux is None:
        def body(h, p):
            return block_fn(p, h), None
    else:
        def body(h, p):
            return block_fn(p, h, aux), None

    out, _ = jax.lax.scan(body, x, stacked_params, unroll=max(1, unroll))
    return out


def _pipeline_telemetry(schedule, pp, M, v, ticks, t0, sample):
    """Host-side schedule telemetry. `sample` is any array flowing through
    the schedule: when it is a tracer the call sits inside an outer jit
    trace, where wall-clock numbers would measure tracing, not execution —
    skip. On the recorded (eager) path the timed window spans trace +
    compile + run of the fused XLA program — each eager call builds a
    fresh closure, so compile dominates and the series is a smoke/debug
    signal, not a perf ruler; production per-step numbers come from the
    profiler's xplane capture, and bubble_fraction (analytic) is exact
    everywhere."""
    if not monitor.enabled() or isinstance(sample, jax.core.Tracer):
        return
    jax.block_until_ready(sample)   # time the run, not just the dispatch
    dt = time.perf_counter() - t0
    # per-tick time ~ per-stage per-microbatch slot time
    monitor.histogram("pipeline/stage_time").labels(
        schedule=schedule).observe(dt / max(1, ticks))
    # warm-up/drain bubble of the schedule: pp-1 idle slots out of
    # M*v + pp - 1 total (v = virtual stages per device; 1F1B has the
    # same fraction over its doubled fwd+bwd slot count)
    monitor.gauge("pipeline/bubble_fraction").labels(schedule=schedule).set(
        (pp - 1) / (M * v + pp - 1))
    monitor.counter("pipeline/microbatches").labels(schedule=schedule).add(M)


_LOW_FLOAT = ("bfloat16", "float16")


def _cpu_lowp() -> bool:
    return jax.default_backend() == "cpu"


def _widen_boundary(tree):
    """CPU-only workaround for the partial-manual bf16 psum bug (see
    _psum_safe): REPLICATED (P()) low-precision inputs to a partial-manual
    shard_map get a JAX-inserted psum over the manual axis on their
    cotangent in the backward pass — in the input dtype, which is the
    crashing construct. Feed such inputs through the boundary as f32 and
    narrow back to the original dtype inside the region (returned as the
    second element, a dtype tree for _narrow_boundary). No-op off-CPU."""
    dtypes = jax.tree_util.tree_map(lambda a: a.dtype, tree)
    if not _cpu_lowp():
        return tree, dtypes
    widened = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if str(a.dtype) in _LOW_FLOAT else a,
        tree)
    return widened, dtypes


def _narrow_boundary(tree, dtypes):
    return jax.tree_util.tree_map(
        lambda a, dt: a.astype(dt) if a.dtype != dt else a, tree, dtypes)


def _psum_safe(x, axis):
    """psum that survives XLA-CPU's float-normalization bug: a bf16/f16
    all-reduce inside a PARTIAL-manual shard_map region (axis_names a
    strict subset of the mesh) hits `Invalid binary instruction opcode
    copy` (fatal) on the CPU backend — minimal repro in
    tests/test_pipeline.py::test_partial_manual_bf16_psum. Shared
    implementation: distributed.collective._reduce_safe (f32 reduce on
    CPU; TPU keeps the native dtype on the wire, half the ICI bytes)."""
    from ..distributed.collective import _reduce_safe

    return _reduce_safe(jax.lax.psum, x, axis)


def pipeline_apply(
    block_fn: Callable,
    stacked_params: Any,
    x,
    n_microbatches: int | None = None,
    axis: str = "pp",
    num_chunks: int = 1,
    aux: Any = None,
):
    """Run x through a pp-stage GPipe pipeline inside one XLA program.

    block_fn(params_leaf_slice, h) -> h : one transformer block.
    stacked_params: pytree, every leaf [L, ...] with L = total blocks,
        L % pp == 0; leading dim sharded on 'pp' outside this call.
    x: [B, ...] activations; split into M micro-batches along dim 0.
    aux: optional pytree of PER-TOKEN metadata (packed-sequence segment
        ids, [B, S]-leading leaves) split into the same M micro-batches as
        x. Unlike activations, aux does NOT hop stages over ICI: every
        stage holds the replicated [M, B/M, ...] table and indexes the
        micro-batch it is currently computing (stage s works on
        micro-batch t - s at tick t), so the id rows stay paired with
        their activations through the whole schedule. When given,
        block_fn is called as block_fn(params, h, aux_mb). This is the
        TPU answer to the reference's p2p meta handshake carrying
        attention masks with activations (pp_utils/p2p_communication.py).

    num_chunks > 1 selects the INTERLEAVED schedule (reference
    meta_parallel/pipeline_parallel.py:461 PipelineParallelWithInterleave):
    each device hosts `num_chunks` non-adjacent layer chunks (virtual
    stage vs hosts layers [vs*k, (vs+1)*k) on device vs % pp), shrinking
    the warm-up/drain bubble from (pp-1)/(M+pp-1) of the step to
    (pp-1)/(M*v+pp-1). See _pipeline_interleaved for the SPMD slot clock.
    """
    mesh = get_mesh()
    pp = axis_size(axis)
    if pp == 1:
        return scan_blocks(block_fn, stacked_params, x, aux=aux)
    if num_chunks > 1:
        return _pipeline_interleaved(block_fn, stacked_params, x,
                                     n_microbatches, axis, num_chunks,
                                     aux=aux)

    B = x.shape[0]
    M = n_microbatches or pp
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible into {M} micro-batches")
    leaves = jax.tree_util.tree_leaves(stacked_params)
    L = leaves[0].shape[0]
    if L % pp != 0:
        raise ValueError(f"{L} blocks not divisible by pp={pp}")

    xs = x.reshape((M, B // M) + x.shape[1:])
    has_aux = aux is not None
    aux_xs = _split_aux(aux, M) if has_aux else ()

    def stage_fn(params, h, amb):
        # params leaves: [k, ...] — this stage's k blocks, scanned rolled:
        # this body repeats inside the pipeline tick loop, so unrolling it
        # would multiply program size per tick.
        return scan_blocks(block_fn, params, h, unroll=1,
                           aux=amb if has_aux else None)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=P(),
        axis_names=frozenset({axis}),
        check_vma=False,
    )
    def run(params, xs, axs):
        # each shard sees leaf [1, k, ...] — drop the stage dim
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        xs = _narrow_boundary(xs, xs_dtype)
        stage = jax.lax.axis_index(axis)
        mb = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)
        fwd_perm = [(i, i + 1) for i in range(pp - 1)]

        def tick(carry, t):
            mb, outs = carry
            # stage 0 ingests micro-batch t (clipped when draining)
            inp = jnp.where(stage == 0, xs[jnp.clip(t, 0, M - 1)], mb)
            # stage s computes micro-batch t - s: its metadata rows come
            # from the replicated table, not the ICI hop
            cur = jnp.clip(t - stage, 0, M - 1)
            amb = jax.tree_util.tree_map(lambda a: a[cur], axs)
            out = stage_fn(params, inp, amb)
            # last stage retires micro-batch t-(pp-1)
            j = t - (pp - 1)
            write = (stage == pp - 1) & (j >= 0)
            outs = jnp.where(
                write,
                jax.lax.dynamic_update_index_in_dim(
                    outs, out, jnp.clip(j, 0, M - 1), 0
                ),
                outs,
            )
            # hop to the next stage over ICI
            mb = jax.lax.ppermute(out, axis, fwd_perm)
            return (mb, outs), None

        (mb, outs), _ = jax.lax.scan(
            tick, (mb, outs), jnp.arange(M + pp - 1)
        )
        # outs is populated only on the last stage; all-reduce over the pp
        # axis broadcasts it (zeros elsewhere).
        outs = jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs))
        return _psum_safe(outs, axis)

    # params arrive stage-major: leaf [L, ...] -> [pp, k, ...] so the shard_map
    # slice along dim 0 hands each stage its k blocks.
    staged = jax.tree_util.tree_map(
        lambda a: a.reshape((pp, L // pp) + a.shape[1:]), stacked_params
    )
    xs, xs_dtype = _widen_boundary(xs)
    # partial-manual shard_map validates specs only under jit; eager calls
    # (plain apply without jit.compile) need the wrapper — it inlines when
    # already inside a trace
    t0 = time.perf_counter()
    with RecordEvent("pipeline/gpipe"):
        out = jax.jit(run)(staged, xs, aux_xs)
    _pipeline_telemetry("gpipe", pp, M, 1, M + pp - 1, t0, out)
    return out.reshape((B,) + x.shape[1:])


def _split_aux(aux, M):
    """Reshape every aux leaf [B, ...] -> [M, B/M, ...] (the same
    micro-batch split as the activations)."""
    def split(a):
        if a.shape[0] % M != 0:
            raise ValueError(
                f"aux leading dim {a.shape[0]} not divisible into {M} "
                "micro-batches (must match the activation batch)")
        return a.reshape((M, a.shape[0] // M) + a.shape[1:])

    return jax.tree_util.tree_map(split, aux)


def _pipeline_interleaved(block_fn, stacked_params, x, n_microbatches,
                          axis, v, aux: Any = None):
    """Interleaved (virtual-stage) pipeline forward in one XLA program.

    The reference drives interleave from the host with a per-rank unit
    ordering (pipeline_parallel.py:461); the SPMD re-derivation used here:
    enumerate per-device work units k = g*(pp*v) + c*pp + j — group g of
    pp micro-batches, chunk c, member j — and run unit k on device s at
    slot u = k + s. Then every dependency arrives exactly one slot early:
    within a chunk, producer (same k, device s-1) finished at u-1; across
    the chunk boundary, device pp-1's unit for chunk c-1 finished at
    (k-pp) + (pp-1) = u-1 and the SAME wraparound ppermute
    [(i, (i+1) % pp)] delivers it. One uniform hop per slot, no
    double-booked devices, bubble = pp-1 slots out of M*v + pp - 1.

    Autodiff-transparent: XLA derives the mirrored backward schedule by
    transposing the scan (activations for all M*v units stay live through
    backward — the memory/bubble trade vs pipeline_1f1b, whose stash ring
    is bounded; the reference's interleave has the same appetite).

    Deliberately NOT merged with the gpipe scan above even though v=1
    degenerates to it: the gpipe body indexes this stage's params
    statically, while this schedule selects the chunk with a traced
    per-slot index — folding gpipe into the v=1 case would put a dynamic
    gather on the hot path of every pp>1 model for no benefit. Fixes to
    either scan body should be mirrored in the other.
    """
    mesh = get_mesh()
    pp = axis_size(axis)
    B = x.shape[0]
    M = n_microbatches or pp
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible into {M} micro-batches")
    if M % pp != 0:
        raise ValueError(
            f"interleaved schedule needs micro-batches ({M}) divisible by "
            f"pp ({pp}) — units advance in groups of pp")
    leaves = jax.tree_util.tree_leaves(stacked_params)
    L = leaves[0].shape[0]
    V = pp * v
    if L % V != 0:
        raise ValueError(f"{L} blocks not divisible by pp*num_chunks={V}")
    k_layers = L // V
    units = M * v
    U = units + pp - 1

    xs = x.reshape((M, B // M) + x.shape[1:])
    has_aux = aux is not None
    aux_xs = _split_aux(aux, M) if has_aux else ()

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(), P()),
        out_specs=P(),
        axis_names=frozenset({axis}),
        check_vma=False,
    )
    def run(params, xs, axs):
        # leaf [1, v, k, ...] -> [v, k, ...]: this device's v chunks
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        xs = _narrow_boundary(xs, xs_dtype)
        stage = jax.lax.axis_index(axis)
        wrap_perm = [(i, (i + 1) % pp) for i in range(pp)]
        mb_shape = xs.shape[1:]

        def tick(carry, u):
            h_recv, outs = carry
            ku = jnp.clip(u - stage, 0, units - 1)
            c = (ku % (pp * v)) // pp
            f = (ku % pp) + pp * (ku // (pp * v))
            chunk_params = jax.tree_util.tree_map(lambda a: a[c], params)
            first = (stage == 0) & (c == 0)
            h_in = jnp.where(first, xs[f], h_recv)
            # metadata for micro-batch f from the replicated table (ids do
            # not hop the ring; the unit->micro-batch map is exact)
            amb = jax.tree_util.tree_map(lambda a: a[f], axs)
            out = scan_blocks(block_fn, chunk_params, h_in, unroll=1,
                              aux=amb if has_aux else None)
            retire = (stage == pp - 1) & (c == v - 1) & (u - stage >= 0) \
                & (u - stage < units)
            outs = jnp.where(
                retire,
                jax.lax.dynamic_update_index_in_dim(outs, out, f, 0),
                outs)
            h_recv = jax.lax.ppermute(out, axis, wrap_perm)
            return (h_recv, outs), None

        carry0 = (jnp.zeros(mb_shape, x.dtype),
                  jnp.zeros((M,) + mb_shape, x.dtype))
        (h, outs), _ = jax.lax.scan(tick, carry0, jnp.arange(U))
        outs = jnp.where(stage == pp - 1, outs, jnp.zeros_like(outs))
        return _psum_safe(outs, axis)

    # layer l lives on virtual stage l // k_layers = c*pp + s: reshape
    # [L,...] -> [V, k, ...] -> [v, pp, k, ...] -> device-major
    # [pp, v, k, ...]
    def stage_major(a):
        rest = a.shape[1:]
        return a.reshape((v, pp, k_layers) + rest).transpose(
            (1, 0, 2) + tuple(range(3, 3 + len(rest))))

    staged = jax.tree_util.tree_map(stage_major, stacked_params)
    xs, xs_dtype = _widen_boundary(xs)
    t0 = time.perf_counter()
    with RecordEvent("pipeline/interleave"):
        out = jax.jit(run)(staged, xs, aux_xs)
    _pipeline_telemetry("interleave", pp, M, v, U, t0, out)
    return out.reshape((B,) + x.shape[1:])


def _label_cotangent(y):
    """Zero cotangent for a (possibly integer) label pytree leaf."""
    if jnp.issubdtype(jnp.result_type(y), jnp.inexact):
        return jnp.zeros_like(y)
    return np.zeros(jnp.shape(y), dtype=jax.dtypes.float0)


def pipeline_1f1b(
    block_fn: Callable,
    loss_fn: Callable,
    stacked_params: Any,
    tail_params: Any,
    x,
    y,
    n_microbatches: int | None = None,
    axis: str = "pp",
    aux: Any = None,
):
    """1F1B (PipeDream-flush) pipelined training loss in ONE XLA program.

    Reference analog: fleet/meta_parallel/pipeline_parallel.py:230 — the
    1F1B steady state where each stage alternates one forward and one
    backward micro-batch so at most `pp - stage` activation stashes are
    live, vs GPipe's M. The reference drives this schedule from the host
    with NCCL p2p; here the whole schedule is a `lax.scan` over global
    "slots" inside one `shard_map`:

    - slot clock: stage s runs forward of micro-batch f at slot `s + 2f`
      and backward of micro-batch b at slot `2*pp - 1 - s + 2b`. The two
      are parity-disjoint, so each slot is one `lax.cond` per stage; in
      steady state every stage computes every slot (no idle beyond the
      pp-1 warmup/drain bubble — the same bubble the reference has).
    - stages stash only their micro-batch INPUT in a pp-deep ring and
      recompute the stage forward under `jax.vjp` at the backward slot
      (activation recompute, the standard large-model 1F1B pairing).
      In-flight memory is O(pp * microbatch), not O(M * activations).
    - hops ride `lax.ppermute` both directions each slot (activations
      s->s+1, cotangents s->s-1) — the p2p_communication.py:298 analog.

    The function is autodiff-transparent: a `jax.custom_vjp` whose primal
    computes loss AND grads in the fused schedule, saving the grads as
    residuals; the outer `jax.grad` then just scales them. `loss_fn`
    consumes `tail_params` on the LAST stage (final norm / lm head /
    criterion), so head grads flow too:

        loss_fn(tail_params, h_out, y_microbatch) -> scalar mean loss

    Returns the scalar mean loss over micro-batches. Grads flow to
    `stacked_params`, `tail_params`, and `x`.

    aux: optional per-token metadata pytree ([B, ...]-leading leaves, e.g.
    packed segment ids) split with the activation micro-batches; when
    given, block_fn is called as block_fn(params, h, aux_mb) — both the
    forward slot (micro-batch f) and the recompute-backward slot
    (micro-batch b) read the right id rows from the replicated table.
    """
    mesh = get_mesh()
    pp = axis_size(axis)
    if pp == 1:
        # Degenerate pipeline: plain differentiable compute (outer autodiff
        # handles grads; no schedule needed).
        out = scan_blocks(block_fn, stacked_params, x, aux=aux)
        return loss_fn(tail_params, out, y)
    return _pipeline_1f1b_vjp(
        block_fn, loss_fn, n_microbatches, axis, stacked_params,
        tail_params, x, y, aux,
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _pipeline_1f1b_vjp(block_fn, loss_fn, n_microbatches, axis,
                       stacked_params, tail_params, x, y, aux):
    loss, _ = _pipeline_1f1b_impl(
        block_fn, loss_fn, n_microbatches, axis, stacked_params,
        tail_params, x, y, aux,
    )
    return loss


def _pipeline_1f1b_fwd(block_fn, loss_fn, n_microbatches, axis,
                       stacked_params, tail_params, x, y, aux):
    loss, grads = _pipeline_1f1b_impl(
        block_fn, loss_fn, n_microbatches, axis, stacked_params,
        tail_params, x, y, aux,
    )
    return loss, (grads, y, aux)


def _pipeline_1f1b_bwd(block_fn, loss_fn, n_microbatches, axis, res, gbar):
    (dparams, dtail, dx), y, aux = res
    # keep each cotangent's dtype: a bare `a * gbar` would promote bf16
    # leaves to f32 and fail custom_vjp's aval check on bf16 models
    scale = lambda t: jax.tree_util.tree_map(
        lambda a: (a * gbar).astype(a.dtype), t)
    dy = jax.tree_util.tree_map(_label_cotangent, y)
    daux = jax.tree_util.tree_map(_label_cotangent, aux)
    return scale(dparams), scale(dtail), (dx * gbar).astype(dx.dtype), dy, daux


_pipeline_1f1b_vjp.defvjp(_pipeline_1f1b_fwd, _pipeline_1f1b_bwd)


def _pipeline_1f1b_impl(block_fn, loss_fn, n_microbatches, axis,
                        stacked_params, tail_params, x, y, aux=None):
    """Fused forward+backward 1F1B schedule. Returns
    (mean_loss, (d_stacked_params, d_tail_params, dx))."""
    mesh = get_mesh()
    pp = axis_size(axis)
    B = x.shape[0]
    M = n_microbatches or pp
    if B % M != 0:
        raise ValueError(f"batch {B} not divisible into {M} micro-batches")
    leaves = jax.tree_util.tree_leaves(stacked_params)
    L = leaves[0].shape[0]
    if L % pp != 0:
        raise ValueError(f"{L} blocks not divisible by pp={pp}")
    R = min(pp, M)                       # stash ring depth (1F1B bound)
    U = 2 * M + 2 * pp - 2               # total schedule slots

    xs = x.reshape((M, B // M) + x.shape[1:])
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:]), y)
    has_aux = aux is not None
    aux_xs = _split_aux(aux, M) if has_aux else ()

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(), P(), P(), P()),
        out_specs=(P(), (P(axis), P(), P())),
        axis_names=frozenset({axis}),
        check_vma=False,
    )
    def run(params, tail, xs, ys, axs):
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        tail = _narrow_boundary(tail, tail_dtype)
        xs = _narrow_boundary(xs, xs_dtype)
        stage = jax.lax.axis_index(axis)
        is_last = stage == pp - 1
        fwd_perm = [(i, i + 1) for i in range(pp - 1)]
        bwd_perm = [(i + 1, i) for i in range(pp - 1)]

        def stage_full(p, tl, h, ymb, amb):
            out = scan_blocks(block_fn, p, h, unroll=1,
                              aux=amb if has_aux else None)
            loss = jax.lax.cond(
                is_last,
                lambda: loss_fn(tl, out, ymb).astype(jnp.float32),
                lambda: jnp.float32(0.0),
            )
            return out, loss

        mb_shape = xs.shape[1:]
        f32 = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.zeros(a.shape, jnp.float32), t)

        carry0 = dict(
            h_recv=jnp.zeros(mb_shape, x.dtype),
            g_recv=jnp.zeros(mb_shape, jnp.float32),
            stash=jnp.zeros((R,) + mb_shape, x.dtype),
            gacc=f32(params),
            tacc=f32(tail),
            dxs=jnp.zeros((M,) + mb_shape, jnp.float32),
            loss_sum=jnp.float32(0.0),
        )

        def slot(carry, u):
            rel_f = u - stage
            do_f = (rel_f >= 0) & (rel_f % 2 == 0) & (rel_f < 2 * M)
            f = jnp.clip(rel_f // 2, 0, M - 1)
            rel_b = u - (2 * pp - 1 - stage)
            do_b = (rel_b >= 0) & (rel_b % 2 == 0) & (rel_b < 2 * M)
            b = jnp.clip(rel_b // 2, 0, M - 1)

            y_f = jax.tree_util.tree_map(lambda a: a[f], ys)
            y_b = jax.tree_util.tree_map(lambda a: a[b], ys)
            aux_f = jax.tree_util.tree_map(lambda a: a[f], axs)
            aux_b = jax.tree_util.tree_map(lambda a: a[b], axs)
            h_in = jnp.where(stage == 0, xs[f], carry["h_recv"])

            def fwd_slot(c):
                out, loss = stage_full(params, tail, h_in, y_f, aux_f)
                return dict(
                    c,
                    stash=jax.lax.dynamic_update_index_in_dim(
                        c["stash"], h_in, f % R, 0),
                    loss_sum=c["loss_sum"] + loss,
                ), out, jnp.zeros(mb_shape, jnp.float32)

            def bwd_slot(c):
                h_stash = c["stash"][b % R]
                g_out = jnp.where(
                    is_last, jnp.zeros(mb_shape, jnp.float32),
                    c["g_recv"]).astype(h_stash.dtype)
                g_loss = jnp.where(is_last, jnp.float32(1.0 / M),
                                   jnp.float32(0.0))
                _, vjp_fn = jax.vjp(
                    lambda p, tl, h: stage_full(p, tl, h, y_b, aux_b),
                    params, tail, h_stash)
                dp, dtl, dh = vjp_fn((g_out, g_loss))
                add = lambda acc, g: jax.tree_util.tree_map(
                    lambda a, b_: a + b_.astype(jnp.float32), acc, g)
                dh32 = dh.astype(jnp.float32)
                dxs = jnp.where(
                    stage == 0,
                    jax.lax.dynamic_update_index_in_dim(c["dxs"], dh32, b, 0),
                    c["dxs"])
                return dict(
                    c,
                    gacc=add(c["gacc"], dp),
                    tacc=add(c["tacc"], dtl),
                    dxs=dxs,
                ), jnp.zeros(mb_shape, x.dtype), dh32

            def idle(c):
                return c, jnp.zeros(mb_shape, x.dtype), \
                    jnp.zeros(mb_shape, jnp.float32)

            c, send_h, send_g = jax.lax.cond(
                do_f, fwd_slot,
                lambda c: jax.lax.cond(do_b, bwd_slot, idle, c),
                carry)
            c = dict(
                c,
                h_recv=jax.lax.ppermute(send_h, axis, fwd_perm),
                g_recv=jax.lax.ppermute(send_g, axis, bwd_perm),
            )
            return c, None

        carry, _ = jax.lax.scan(slot, carry0, jnp.arange(U))

        loss = jax.lax.psum(carry["loss_sum"], axis) / M  # f32 scalar
        # tail/dx live on one stage (zeros elsewhere) — psum broadcasts.
        tacc = jax.tree_util.tree_map(
            lambda a: _psum_safe(a, axis), carry["tacc"])
        dxs = _psum_safe(carry["dxs"], axis)
        gacc = jax.tree_util.tree_map(lambda a: a[None], carry["gacc"])
        return loss, (gacc, tacc, dxs)

    staged = jax.tree_util.tree_map(
        lambda a: a.reshape((pp, L // pp) + a.shape[1:]), stacked_params
    )
    tail_params, tail_dtype = _widen_boundary(tail_params)
    xs, xs_dtype = _widen_boundary(xs)
    # see pipeline_apply: jit makes eager invocation legal (inlines in-trace)
    t0 = time.perf_counter()
    with RecordEvent("pipeline/1f1b"):
        loss, (gacc, tacc, dxs) = jax.jit(run)(
            staged, tail_params, xs, ys, aux_xs)
    _pipeline_telemetry("1f1b", pp, M, 1, U, t0, loss)
    dparams = jax.tree_util.tree_map(
        lambda g, p: g.reshape((L,) + g.shape[2:]).astype(p.dtype),
        gacc, stacked_params)
    dtail = jax.tree_util.tree_map(
        lambda g, dt: g.astype(dt), tacc, tail_dtype)
    dx = dxs.reshape((B,) + x.shape[1:]).astype(x.dtype)
    return loss, (dparams, dtail, dx)
