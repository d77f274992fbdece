"""Functional tests for the dual-mode collective API
(paddle.distributed.{all_reduce,reduce_scatter,...} — reference
python/paddle/distributed/communication/; SURVEY §2.4 collective comm
API). Runs inside shard_map regions over a mesh axis, matching the
reference's collective_*_api.py two-rank numpy-parity scripts — here the
8-virtual-device CPU mesh stands in for the pod.

Includes bf16 coverage: low-precision all-reduce inside a partial-manual
shard region used to crash XLA-CPU fatally (see
parallel/pipeline.py:_psum_safe); collective.py routes reduces through
the same f32-on-CPU workaround.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import pytest

pytestmark = pytest.mark.slow

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import parallel
from paddle_tpu.core.tensor import Tensor


def _run_sharded(fn, arr, axis="dp"):
    """Run fn(Tensor)->Tensor under shard_map over `axis` (partial-manual,
    like the framework's own parallel layers)."""
    import functools
    from paddle_tpu.parallel.mesh import get_mesh

    mesh = get_mesh()
    group = dist.new_group(axis_name=axis)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P(axis),
                       out_specs=P(axis), axis_names=frozenset({axis}),
                       check_vma=False)
    def body(a):
        return fn(Tensor(a), group)._data

    return np.asarray(jax.jit(body)(arr), np.float32)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"],
                         ids=["f32", "bf16"])
def test_all_reduce_sum_parity(dtype):
    parallel.init_mesh(dp=4)
    rng = np.random.RandomState(0)
    x = rng.randn(4, 2, 8).astype(np.float32)
    arr = jnp.asarray(x, jnp.bfloat16 if dtype == "bfloat16" else jnp.float32)

    out = _run_sharded(lambda t, g: dist.all_reduce(t, group=g), arr)
    # each shard holds the sum over the axis
    np.testing.assert_allclose(out, np.repeat(x.sum(0, keepdims=True), 4, 0),
                               rtol=2e-2, atol=2e-2)


def test_all_reduce_max_min():
    parallel.init_mesh(dp=4)
    rng = np.random.RandomState(1)
    x = rng.randn(4, 2, 8).astype(np.float32)
    arr = jnp.asarray(x)
    out_max = _run_sharded(
        lambda t, g: dist.all_reduce(t, op=dist.ReduceOp.MAX, group=g), arr)
    np.testing.assert_allclose(
        out_max, np.repeat(x.max(0, keepdims=True), 4, 0), rtol=1e-6)
    out_min = _run_sharded(
        lambda t, g: dist.all_reduce(t, op=dist.ReduceOp.MIN, group=g), arr)
    np.testing.assert_allclose(
        out_min, np.repeat(x.min(0, keepdims=True), 4, 0), rtol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"],
                         ids=["f32", "bf16"])
def test_bf16_all_reduce_in_bf16_model_grads(dtype):
    """End-to-end: manual grad all-reduce (fleet-DP style) on a bf16
    tensor inside a shard region must not crash and must sum."""
    parallel.init_mesh(dp=2)
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    g = jnp.asarray(np.arange(2 * 4 * 128).reshape(2, 4, 128), dt)
    out = _run_sharded(lambda t, gr: dist.all_reduce(t, group=gr), g)
    want = np.asarray(g, np.float32).sum(0, keepdims=True).repeat(2, 0)
    np.testing.assert_allclose(out, want, rtol=2e-2, atol=2.0)


def test_all_reduce_prod_and_reduce_scatter_max():
    parallel.init_mesh(dp=4)
    rng = np.random.RandomState(2)
    x = np.abs(rng.randn(4, 2, 8)).astype(np.float32) + 0.5
    out = _run_sharded(
        lambda t, g: dist.all_reduce(t, op=dist.ReduceOp.PROD, group=g),
        jnp.asarray(x))
    np.testing.assert_allclose(out, np.repeat(x.prod(0, keepdims=True), 4, 0),
                               rtol=1e-5)

    # reduce_scatter with MAX: reduce over members, member i keeps chunk i
    # (global [8, 8] -> local [4, 8] per member -> local out [2, 8];
    # restacking the members' chunks reassembles the full reduced array)
    parallel.init_mesh(dp=2)
    y = rng.randn(8, 8).astype(np.float32)
    out = _run_sharded(
        lambda t, g: dist.reduce_scatter(t, op=dist.ReduceOp.MAX, group=g),
        jnp.asarray(y))
    full = np.maximum(y[:4], y[4:])                # [4, 8] reduced
    np.testing.assert_allclose(out, full, rtol=1e-6)


def test_broadcast_allgather_alltoall():
    import functools
    from paddle_tpu.parallel.mesh import get_mesh

    parallel.init_mesh(dp=4)
    mesh = get_mesh()
    group = dist.new_group(axis_name="dp")
    rng = np.random.RandomState(3)
    x = rng.randn(4, 2, 8).astype(np.float32)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), axis_names=frozenset({"dp"}),
                       check_vma=False)
    def bcast(a):
        return dist.broadcast(Tensor(a), src=2, group=group)._data

    out = np.asarray(jax.jit(bcast)(jnp.asarray(x)), np.float32)
    np.testing.assert_allclose(out, np.repeat(x[2:3], 4, 0), rtol=1e-6)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), axis_names=frozenset({"dp"}),
                       check_vma=False)
    def gathered_sum(a):
        parts = dist.all_gather([], Tensor(a), group=group)
        acc = parts[0]
        for p in parts[1:]:
            acc = acc + p
        return acc._data

    out = np.asarray(jax.jit(gathered_sum)(jnp.asarray(x)), np.float32)
    np.testing.assert_allclose(out, np.repeat(x.sum(0, keepdims=True), 4, 0),
                               rtol=1e-5)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), axis_names=frozenset({"dp"}),
                       check_vma=False)
    def a2a(a):
        # member i sends chunk j to member j: with every member holding
        # [4, 8] (4 chunks of [1, 8]), alltoall transposes chunk ownership
        ins = [Tensor(a[0, j:j + 1]) for j in range(4)]
        outs = dist.alltoall(ins, group=group)
        return jnp.stack([o._data for o in outs])[None]

    y = rng.randn(4, 4, 1, 8).astype(np.float32)
    out = np.asarray(jax.jit(a2a)(jnp.asarray(y)), np.float32)
    want = y.transpose(1, 0, 2, 3)       # chunk ownership transposed
    np.testing.assert_allclose(out.reshape(want.shape), want, rtol=1e-6)


def test_stream_variants():
    """paddle.distributed.stream.* (reference communication/stream/):
    same collectives; sync_op=False returns a born-done task handle (XLA
    owns the overlap the reference managed with comm/calc streams)."""
    import functools
    from paddle_tpu.parallel.mesh import get_mesh
    from paddle_tpu.distributed import stream as dstream

    parallel.init_mesh(dp=4)
    mesh = get_mesh()
    group = dist.new_group(axis_name="dp")
    rng = np.random.RandomState(5)
    x = rng.randn(4, 2, 8).astype(np.float32)

    captured = {}

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=P("dp"),
                       out_specs=P("dp"), axis_names=frozenset({"dp"}),
                       check_vma=False)
    def body(a):
        t = Tensor(a)
        # reference idiom: task returned for BOTH sync modes; wait() is
        # immediate under XLA
        task = dstream.all_reduce(t, group=group)
        captured["task"] = task
        task2 = dstream.all_reduce(t, sync_op=False, group=group,
                                   use_calc_stream=True)
        captured["task2"] = task2
        return t._data

    out = np.asarray(jax.jit(body)(jnp.asarray(x)), np.float32)
    # two all-reduces: sum over axis, then sum of the (replicated) sums x4
    want = np.repeat(x.sum(0, keepdims=True), 4, 0) * 4
    np.testing.assert_allclose(out, want, rtol=1e-5)
    assert captured["task"].is_completed() and captured["task"].wait()
    assert captured["task2"].is_completed() and captured["task2"].wait()


def test_global_scatter_gather_uniform_capacity():
    """distributed.utils.global_scatter/global_gather (reference
    moe_utils.py:20,137): world-1 identity + uniform-capacity SPMD
    all-to-all round trip over the dp axis."""
    from paddle_tpu.distributed.utils import global_scatter, global_gather

    # world == 1: identity with gradient flow
    x = paddle.to_tensor(np.arange(8, dtype="float32").reshape(4, 2))
    x.stop_gradient = False
    lc = paddle.to_tensor(np.array([2, 2], np.int64))
    out = global_scatter(x, lc, lc)
    np.testing.assert_array_equal(out.numpy(), x.numpy())
    global_gather(out, lc, lc).sum().backward()
    np.testing.assert_array_equal(x.grad.numpy(), np.ones((4, 2)))

    # uniform capacity across an 8-way dp axis: scatter then gather
    # round-trips every row to its origin
    parallel.init_mesh(dp=8)
    world = 8
    cap, n_expert, d = 2, 1, 4
    counts = paddle.to_tensor(np.full(world * n_expert, cap, np.int64))
    rows = world * world * n_expert * cap  # global view: per-shard w*e*cap
    data = np.arange(rows * d, dtype=np.float32).reshape(rows, d)

    def run(fn):
        import functools
        from paddle_tpu.parallel.mesh import get_mesh
        group = dist.new_group(axis_name="dp")

        @functools.partial(jax.shard_map, mesh=get_mesh(), in_specs=P("dp"),
                           out_specs=P("dp"), axis_names=frozenset({"dp"}),
                           check_vma=False)
        def body(a):
            return fn(Tensor(a), group)._data

        return np.asarray(jax.jit(body)(data), np.float32)

    scattered = run(lambda t, g: global_scatter(t, counts, counts, group=g))
    assert scattered.shape == data.shape
    assert not np.array_equal(scattered, data)  # rows really moved
    round_trip = run(lambda t, g: global_gather(
        global_scatter(t, counts, counts, group=g), counts, counts, group=g))
    np.testing.assert_array_equal(round_trip, data)
