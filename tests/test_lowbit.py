"""paddle_tpu.lowbit — the real int8/int4 runtime (ISSUE 4).

The bar: (1) weight-only int8/int4 Linears track fp32 within documented
tolerance and the quantize/pack/unpack path round-trips EXACTLY; (2) an
int8-KV `LLMEngine` produces greedy decodes matching the fp engine within
tolerance on the test GPT while its pool holds ≥1.9× the blocks for the
same bytes, with fork/evict/swap bit-stable in the quantized domain;
(3) int8 all-reduce is exact on int8-representable values and an
MNIST-scale DP run converges with ``compress="int8"`` + error feedback.
"""
import functools
import os
import pathlib
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
import pytest

import paddle_tpu as paddle
from paddle_tpu import lowbit, monitor, nn, optimizer, parallel
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.lowbit import (WeightOnlyLinear, pack_int4_arrays,
                               quantize_absmax_arrays, dequantize_arrays,
                               quantize_for_inference,
                               quantized_all_reduce_arrays,
                               quantized_matmul_arrays, unpack_int4_arrays)
from paddle_tpu.models import GPTForCausalLM, gpt_test_config
from paddle_tpu.ops.paged_attention import (quantized_cache_update_arrays,
                                            quantized_gather_kv_arrays)
from paddle_tpu.serving import BlockKVCache, EngineConfig, LLMEngine, \
    SamplingParams


# ---------------------------------------------------------------------------
# wing 1: weight-only quantized inference
# ---------------------------------------------------------------------------
class TestQuantizePackUnpack:
    def test_int4_pack_unpack_exact_roundtrip(self):
        rng = np.random.RandomState(0)
        for rows in (6, 7):                       # even AND odd first dim
            q = rng.randint(-7, 8, (rows, 5)).astype(np.int8)
            packed = pack_int4_arrays(q)
            assert packed.shape == ((rows + 1) // 2, 5)
            assert packed.dtype == jnp.uint8
            back = unpack_int4_arrays(packed, rows)
            np.testing.assert_array_equal(np.asarray(back), q)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_grid_values_roundtrip_exact(self, bits):
        """Values already on the quantization grid survive q->dq exactly."""
        qmax = lowbit.qmax_for_bits(bits)
        scale = 0.125
        w = (np.arange(-qmax, qmax + 1) * scale).astype(np.float32)[:, None]
        q, s = quantize_absmax_arrays(w, bits=bits, axis=0)
        np.testing.assert_array_equal(
            np.asarray(dequantize_arrays(q, s, axis=1)), w)

    @pytest.mark.parametrize("bits", [8, 4])
    def test_dequant_error_bounded_by_half_step(self, bits):
        rng = np.random.RandomState(1)
        w = rng.randn(64, 16).astype(np.float32)
        q, s = quantize_absmax_arrays(w, bits=bits, axis=0)
        err = np.abs(np.asarray(dequantize_arrays(q, s, axis=1)) - w)
        # |x - q*s| <= s/2 per channel (round-to-nearest)
        assert (err <= np.asarray(s)[None, :] / 2 + 1e-7).all()

    def test_zero_tensor_quantizes_to_exact_zero(self):
        q, s = quantize_absmax_arrays(np.zeros((8, 3), np.float32), axis=0)
        assert np.asarray(q).max() == 0 and float(np.asarray(s).max()) == 0.0
        np.testing.assert_array_equal(
            np.asarray(dequantize_arrays(q, s, axis=1)), 0.0)


class TestWeightOnlyLinear:
    @pytest.mark.parametrize("dtype,tol", [("int8", 0.02), ("int4", 0.3)])
    def test_parity_vs_fp32(self, dtype, tol):
        paddle.seed(0)
        lin = nn.Linear(33, 17)                  # odd in_features: int4 pad
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(4, 33).astype(np.float32))
        ref = lin(x).numpy()
        wol = WeightOnlyLinear.from_linear(lin, dtype)
        out = wol(x).numpy()
        rel = np.abs(out - ref).max() / np.abs(ref).max()
        assert rel <= tol, rel
        # scales cost 4·out bytes, so tiny layers sit a bit above the
        # asymptotic 4×/8× code-only ratios
        assert wol.packed_bytes < wol.dense_bytes / (3.5 if dtype == "int8"
                                                     else 6)

    def test_scale_after_matmul_equals_dequant_then_matmul(self):
        """(x @ q) * scale must equal x @ (q * scale) — the in-kernel
        dequant is a reassociation, not an approximation (per-channel
        scale is constant along the contraction)."""
        rng = np.random.RandomState(2)
        x = rng.randn(5, 12).astype(np.float32)
        w = rng.randn(12, 7).astype(np.float32)
        q, s = quantize_absmax_arrays(w, bits=8, axis=0)
        fused = np.asarray(quantized_matmul_arrays(x, q, s))
        explicit = x @ np.asarray(dequantize_arrays(q, s, axis=1))
        np.testing.assert_allclose(fused, explicit, rtol=1e-5, atol=1e-5)

    def test_swap_deep_model_and_state_dict_roundtrip(self):
        paddle.seed(0)

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.fc = nn.Linear(16, 32)       # attribute-referenced
                self.head = nn.Sequential(nn.Linear(32, 8), nn.ReLU())

            def forward(self, x):
                return self.head(self.fc(x))

        net = Net()
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(4, 16).astype(np.float32))
        ref = net(x).numpy()
        qnet = quantize_for_inference(net, "int8")
        # the attribute mirror must see the swap too (forward says self.fc)
        assert isinstance(qnet.fc, WeightOnlyLinear)
        assert isinstance(net.fc, nn.Linear), "original must be untouched"
        out = qnet(x).numpy()
        assert np.abs(out - ref).max() / np.abs(ref).max() < 0.05
        # packed codes + scales ride state_dict
        q2 = quantize_for_inference(net, "int8")
        q2.set_state_dict(qnet.state_dict())
        np.testing.assert_array_equal(q2(x).numpy(), out)

    def test_gpt_greedy_decode_matches_fp(self):
        """Weight-only int8 on the per-layer test GPT: greedy decode
        agrees with fp32 (documented tolerance: ≥90% token agreement;
        measured 100% on the test config)."""
        parallel.init_mesh()        # a leaked mp>1 mesh from an earlier
        #                             suite would veto the mp-linear swap
        paddle.seed(0)
        cfg = gpt_test_config(stacked_blocks=False, sequence_parallel=False)
        m = GPTForCausalLM(cfg)
        m.eval()
        rng = np.random.RandomState(0)
        ids = Tensor(jnp.asarray(
            rng.randint(0, cfg.vocab_size, (2, 6)).astype(np.int32)))
        ref = np.asarray(m.generate(ids, max_new_tokens=8)._data)
        qm = quantize_for_inference(m, "int8")
        assert sum(1 for l in qm.sublayers()
                   if isinstance(l, WeightOnlyLinear)) > 0
        out = np.asarray(qm.generate(ids, max_new_tokens=8)._data)
        agree = (ref[:, 6:] == out[:, 6:]).mean()
        assert agree >= 0.9, agree


class TestQuantizationKitIntegration:
    def test_ptq_convert_targets_weight_only(self):
        from paddle_tpu.quantization import PTQ, _FixedQDQ

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(), nn.Linear(32, 8))
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
        ref = net(x).numpy()
        ptq = PTQ()
        qm = ptq.quantize(net)
        for _ in range(3):
            qm(x)
        conv = ptq.convert(qm, weight_only="int8")
        kinds = [type(l) for l in conv.sublayers()]
        assert WeightOnlyLinear in kinds and _FixedQDQ in kinds
        out = conv(x).numpy()
        assert np.abs(out - ref).max() / np.abs(ref).max() < 0.05

    def test_qat_convert_flows_trained_scale(self):
        from paddle_tpu.quantization import (QAT, QuantConfig,
                                             FakeQuanterWithAbsMaxObserver)

        paddle.seed(0)
        net = nn.Sequential(nn.Linear(16, 8))
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(8, 16).astype(np.float32))
        qat = QAT(QuantConfig(activation=FakeQuanterWithAbsMaxObserver,
                              weight=None))
        qm = qat.quantize(net)
        qm.train()
        qm(x)
        qm.eval()
        conv = qat.convert(qm, weight_only="int8")
        wol = next(l for l in conv.sublayers()
                   if isinstance(l, WeightOnlyLinear))
        # per-tensor scale = trained absmax / 127
        w = net[0].weight.numpy()
        np.testing.assert_allclose(float(wol.scale._data),
                                   np.abs(w).max() / 127.0, rtol=1e-5)

    def test_observers_run_device_side_under_trace(self):
        """The PTQ observers must be traceable (pure-jnp buffer updates):
        the old np.asarray round-trip was a device→host sync per
        calibration batch and a hard error under jit."""
        from paddle_tpu.quantization import (AbsmaxObserver,
                                             PassthroughWeightObserver)

        def run_obs(a):
            obs = AbsmaxObserver()
            obs.forward(Tensor(a))
            return obs._max._data

        out = jax.jit(run_obs)(jnp.asarray([1.0, -3.0, 2.0]))
        assert float(out) == 3.0

        def run_wobs(a):
            obs = PassthroughWeightObserver()
            obs.forward(Tensor(a))
            return obs._scale._data

        out = jax.jit(run_wobs)(jnp.asarray([-0.5, 0.25]))
        assert float(out) == 0.5

    def test_absmax_observer_running_max(self):
        from paddle_tpu.quantization import AbsmaxObserver

        obs = AbsmaxObserver()
        obs.forward(paddle.to_tensor(np.asarray([1.0, -2.0], np.float32)))
        obs.forward(paddle.to_tensor(np.asarray([0.5], np.float32)))
        assert float(obs.scales()._data) == 2.0    # max survives batch 2

    def test_qdq_inference_matches_ste_forward(self):
        from paddle_tpu.quantization import _fake_quant_ste, _qdq

        rng = np.random.RandomState(3)
        x = paddle.to_tensor(rng.randn(32).astype(np.float32))
        s = paddle.to_tensor(np.asarray(1.7, np.float32))
        np.testing.assert_array_equal(
            _qdq(x, s, 8).numpy(), _fake_quant_ste(x, s, 8).numpy())


# ---------------------------------------------------------------------------
# wing 2: quantized KV cache serving
# ---------------------------------------------------------------------------
NEW = 5
LENS = [3, 5, 7, 3, 5, 7, 4, 4]


@pytest.fixture(scope="module")
def model():
    cfg = gpt_test_config(stacked_blocks=True, sequence_parallel=False)
    paddle.seed(0)
    m = GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def prompts(model):
    rng = np.random.RandomState(0)
    return [rng.randint(0, model.cfg.vocab_size, (n,)).astype(np.int32)
            for n in LENS]


class TestQuantizedKVCache:
    def test_block_capacity_at_least_1p9x_same_bytes(self, model):
        fp = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4))
        q8 = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=4,
                                           kv_cache_dtype="int8"))
        assert q8.cache.pool_bytes <= fp.cache.pool_bytes
        assert q8.cache.num_blocks >= 1.9 * fp.cache.num_blocks
        # the per-block accounting itself, fp32 and bf16
        for dt, floor in ((jnp.float32, 3.0), (jnp.bfloat16, 1.9)):
            ratio = BlockKVCache.block_bytes(16, 4, 8, dt) \
                / BlockKVCache.block_bytes(16, 4, 8, dt, "int8")
            assert ratio >= floor, (dt, ratio)

    def test_greedy_parity_within_tolerance(self, model, prompts):
        """int8-KV greedy decode vs the fp engine: ≥90% token agreement
        (documented tolerance; measured 100% on the test GPT)."""
        fp = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=8))
        q8 = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=8,
                                           kv_cache_dtype="int8"))
        sp = SamplingParams(max_new_tokens=NEW)
        o_fp = fp.generate(prompts, sp)
        o_q8 = q8.generate(prompts, sp)
        agree = tot = 0
        for a, b, p in zip(o_fp, o_q8, prompts):
            agree += int((a[len(p):] == b[len(p):]).sum())
            tot += NEW
        assert agree / tot >= 0.9, (agree, tot)
        assert q8.cache.blocks_in_use == 0

    def test_evict_swap_bit_stable_in_quantized_domain(self, model):
        """Forcing eviction churn must not change a single token vs an
        unpressured int8 engine: swap saves/restores CODES + SCALES
        bit-exactly."""
        rng = np.random.RandomState(1)
        pa = rng.randint(0, model.cfg.vocab_size, (14,)).astype(np.int32)
        pb = rng.randint(0, model.cfg.vocab_size, (15,)).astype(np.int32)
        sp = SamplingParams(max_new_tokens=NEW)
        big = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2,
                                            kv_cache_dtype="int8"))
        ref = big.generate([pa, pb], sp)
        small = LLMEngine(model, EngineConfig(block_size=16, num_blocks=3,
                                              max_num_seqs=2,
                                              kv_cache_dtype="int8"))
        outs = small.generate([pa, pb], sp)
        assert small._m_preempt.value >= 1 or not monitor.enabled()
        np.testing.assert_array_equal(ref[0], outs[0])
        np.testing.assert_array_equal(ref[1], outs[1])

    def test_fork_does_not_perturb_parent(self, model):
        rng = np.random.RandomState(2)
        prompt = rng.randint(0, model.cfg.vocab_size, (20,)).astype(np.int32)
        base = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2,
                                             kv_cache_dtype="int8"))
        [solo] = base.generate([prompt], SamplingParams(max_new_tokens=NEW))
        eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2,
                                            kv_cache_dtype="int8"))
        parent = eng.add_request(prompt, SamplingParams(max_new_tokens=NEW))
        eng.step()                      # prefill + first token
        child = eng.fork_request(parent, SamplingParams(max_new_tokens=NEW))
        while eng.has_unfinished():
            eng.step()
        np.testing.assert_array_equal(solo, eng.request_output(parent))
        # greedy child continues the same prefix: its stream re-joins the
        # parent's (offset by the one re-fed token)
        child_out = eng.request_output(child)
        assert len(child_out) == 21 + NEW
        np.testing.assert_array_equal(child_out[:21 + NEW - 1],
                                      eng.request_output(parent)[:25])
        eng.release_request(parent)
        eng.release_request(child)

    def test_quantized_update_unit(self):
        """Array-level contract of the quantizing scatter: dequant ≈
        written rows; writes that do NOT raise a block's amax leave
        existing codes bit-identical."""
        nb, bs, h, d = 4, 4, 2, 3
        blocks = jnp.zeros((nb, bs, h * d), jnp.int8)
        scales = jnp.zeros((nb, h), jnp.float32)
        rng = np.random.RandomState(0)
        rows = jnp.asarray(rng.randn(1, 4, h, d).astype(np.float32))
        slots = jnp.asarray([[0, 1, 2, 3]], jnp.int32)   # block 0
        b1, s1 = quantized_cache_update_arrays(blocks, scales, rows, slots)
        table = jnp.asarray([[0]], jnp.int32)
        deq = np.asarray(quantized_gather_kv_arrays(b1, s1, table))
        np.testing.assert_allclose(deq[0, :4], np.asarray(rows)[0],
                                   atol=float(s1.max()) / 2 + 1e-7)
        # smaller-magnitude write into block 1: block 0 codes untouched
        small = rows * 0.1
        slots2 = jnp.asarray([[4, 5, 6, 7]], jnp.int32)
        b2, s2 = quantized_cache_update_arrays(b1, s1, small, slots2)
        np.testing.assert_array_equal(np.asarray(b2[0]), np.asarray(b1[0]))
        np.testing.assert_array_equal(np.asarray(s2[0]), np.asarray(s1[0]))
        # out-of-range slots are dropped, not clamped
        b3, s3 = quantized_cache_update_arrays(
            b2, s2, rows * 100, jnp.full((1, 4), nb * bs, jnp.int32))
        np.testing.assert_array_equal(np.asarray(b3), np.asarray(b2))
        np.testing.assert_array_equal(np.asarray(s3), np.asarray(s2))

    def test_swap_roundtrip_bit_exact_with_scales(self):
        cache = BlockKVCache(num_layers=2, num_blocks=6, block_size=4,
                             num_heads=2, head_dim=3, kv_quant="int8")
        rng = np.random.RandomState(4)
        cache.allocate("a", 7)
        idx = jnp.asarray(cache._tables["a"], jnp.int32)
        for l in range(2):
            cache.k_blocks[l] = cache.k_blocks[l].at[idx].set(
                jnp.asarray(rng.randint(-127, 128, (2, 4, 2 * 3)), jnp.int8))
            cache.k_scales[l] = cache.k_scales[l].at[idx].set(
                jnp.asarray(rng.rand(2, 2), jnp.float32))
        kb = [np.asarray(k[idx]) for k in cache.k_blocks]
        ks = [np.asarray(s[idx]) for s in cache.k_scales]
        saved = cache.swap_out("a")
        cache.allocate("b", 9)          # churn the free list
        cache.swap_in("a", saved)
        idx2 = jnp.asarray(cache._tables["a"], jnp.int32)
        for l in range(2):
            np.testing.assert_array_equal(
                np.asarray(cache.k_blocks[l][idx2]), kb[l])
            np.testing.assert_array_equal(
                np.asarray(cache.k_scales[l][idx2]), ks[l])

    def test_reallocated_block_resets_scales(self):
        cache = BlockKVCache(num_layers=1, num_blocks=2, block_size=4,
                             num_heads=1, head_dim=2, kv_quant="int8")
        cache.allocate("a", 8)
        cache.k_scales[0] = cache.k_scales[0].at[:].set(7.0)
        cache.free("a")
        cache.allocate("b", 8)
        assert float(np.asarray(cache.k_scales[0]).max()) == 0.0

    def test_rejects_unknown_kv_quant(self, model):
        with pytest.raises(ValueError):
            BlockKVCache(1, 4, 16, 2, 4, kv_quant="int4")
        with pytest.raises(ValueError):
            LLMEngine(model, EngineConfig(kv_cache_dtype="fp8"))


# ---------------------------------------------------------------------------
# wing 3: quantized collectives
# ---------------------------------------------------------------------------
def _shard4(fn, *arrays):
    """Run fn(*per-shard arrays) under shard_map over dp=4; inputs/outputs
    carry a leading member axis of 4."""
    from paddle_tpu.parallel.mesh import get_mesh

    parallel.init_mesh(dp=4)
    mesh = get_mesh()
    n = len(arrays)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(P("dp"),) * n,
                       out_specs=P("dp"), axis_names=frozenset({"dp"}),
                       check_vma=False)
    def body(*shards):
        return fn(*shards)

    return np.asarray(jax.jit(body)(*arrays))


class TestQuantizedCollectives:
    def test_exact_on_int8_representable_values(self):
        rng = np.random.RandomState(0)
        ints = rng.randint(-127, 128, (4, 64)).astype(np.float32)
        ints[:, 0] = 127.0              # pins every chunk's shared scale
        got = _shard4(
            lambda s: quantized_all_reduce_arrays(s, "dp", chunk=32)[0],
            ints)
        np.testing.assert_array_equal(got, ints.sum(0, keepdims=True)
                                      .repeat(4, 0))

    def test_close_on_arbitrary_floats(self):
        rng = np.random.RandomState(1)
        a = rng.randn(4, 37).astype(np.float32)   # odd size: chunk padding
        got = _shard4(
            lambda s: quantized_all_reduce_arrays(s, "dp", chunk=16,
                                                  average=True)[0], a)
        want = a.mean(0, keepdims=True).repeat(4, 0)
        assert np.abs(got - want).max() / np.abs(want).max() < 0.02

    def test_all_gather_dequantizes_every_shard(self):
        rng = np.random.RandomState(2)
        a = rng.randn(4, 21).astype(np.float32)
        got = _shard4(
            lambda s: lowbit.quantized_all_gather_arrays(
                s, "dp", chunk=8).reshape(1, -1), a)
        for m in range(4):
            part = got[m].reshape(4, 21)
            assert np.abs(part - a).max() / np.abs(a).max() < 0.02

    def test_collective_api_compress(self):
        import paddle_tpu.distributed as dist

        parallel.init_mesh(dp=4)
        group = dist.new_group(axis_name="dp")
        rng = np.random.RandomState(3)
        a = rng.randn(4, 33).astype(np.float32)
        got = _shard4(
            lambda s: dist.all_reduce(Tensor(s), group=group,
                                      compress="int8")._data, a)
        want = a.sum(0, keepdims=True).repeat(4, 0)
        assert np.abs(got - want).max() / np.abs(want).max() < 0.02
        # eager world=1: identity
        t = paddle.to_tensor(a)
        assert dist.all_reduce(t, compress="int8") is t
        # loud rejection of unsupported modes
        with pytest.raises(ValueError):
            dist.all_reduce(t, op=dist.ReduceOp.MAX, compress="int8")
        with pytest.raises(ValueError):
            dist.all_reduce(t, compress="int4")

    def test_compression_ratio_metric(self):
        if not monitor.enabled():
            pytest.skip("PTPU_MONITOR disabled")
        monitor.reset()
        rng = np.random.RandomState(4)
        a = rng.randn(4, 256).astype(np.float32)
        _shard4(lambda s: quantized_all_reduce_arrays(s, "dp")[0], a)
        snap = monitor.snapshot()
        key = [k for k in snap if k.startswith("lowbit/comm_compression")]
        assert key, sorted(snap)
        val = snap[key[0]]
        ratio = max(float(v) for v in
                    (val.values() if isinstance(val, dict) else [val]))
        assert 3.0 < ratio <= 4.0, val

    def test_error_feedback_recovers_lost_signal(self):
        """50 repeated reductions of the same vector: with EF the running
        sum tracks the true mean far better than one-shot noise."""
        from paddle_tpu.parallel.mesh import get_mesh

        parallel.init_mesh(dp=4)
        mesh = get_mesh()
        rng = np.random.RandomState(5)
        a = rng.randn(4, 37).astype(np.float32)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P("dp"), P("dp")),
                           out_specs=(P("dp"), P("dp")),
                           axis_names=frozenset({"dp"}), check_vma=False)
        def body(s, res):
            out, nres = quantized_all_reduce_arrays(
                s, "dp", chunk=16, residual=res, average=True)
            return out, nres

        step = jax.jit(body)
        res = np.zeros_like(a)
        acc = np.zeros((37,))
        for _ in range(50):
            out, res = step(a, np.asarray(res))
            acc += np.asarray(out)[0]
        true = a.mean(0) * 50
        rel = np.abs(acc - true).max() / np.abs(true).max()
        assert rel < 2e-3, rel            # one-shot noise is ~5e-3/step

    def test_collective_api_error_feedback_buffer(self):
        """`all_reduce(..., error_feedback=buf)` must rewrite the buffer
        with the local rounding residual (nonzero for off-grid values)."""
        import paddle_tpu.distributed as dist
        from paddle_tpu.parallel.mesh import get_mesh

        parallel.init_mesh(dp=4)
        mesh = get_mesh()
        group = dist.new_group(axis_name="dp")
        rng = np.random.RandomState(6)
        a = rng.randn(4, 33).astype(np.float32)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P("dp"), P("dp")),
                           out_specs=(P("dp"), P("dp")),
                           axis_names=frozenset({"dp"}), check_vma=False)
        def body(s, r):
            ef = Tensor(r[0])
            out = dist.all_reduce(Tensor(s), op=dist.ReduceOp.AVG,
                                  group=group, compress="int8",
                                  error_feedback=ef)
            return out._data, ef._data[None]

        out, res = jax.jit(body)(a, np.zeros((4, 1, 33), np.float32))
        want = a.mean(0)
        assert np.abs(np.asarray(out)[0] - want).max() \
            / np.abs(want).max() < 0.02
        assert float(np.abs(np.asarray(res)).max()) > 0

    def test_meta_optimizer_noop_under_gspmd(self):
        from paddle_tpu.distributed.fleet.meta_optimizers import \
            QuantAllReduceOptimizer

        paddle.seed(0)
        m = nn.Linear(8, 4)
        ref = nn.Linear(8, 4)
        ref.set_state_dict(m.state_dict())
        io = optimizer.SGD(learning_rate=0.1, parameters=ref.parameters())
        qo = QuantAllReduceOptimizer(
            optimizer.SGD(learning_rate=0.1, parameters=m.parameters()))
        rng = np.random.RandomState(0)
        x = paddle.to_tensor(rng.randn(16, 8).astype(np.float32))
        y = paddle.to_tensor(rng.randn(16, 4).astype(np.float32))
        for _ in range(3):
            l1 = ((ref(x) - y) ** 2).mean()
            l1.backward(); io.step(); io.clear_grad()
            l2 = ((m(x) - y) ** 2).mean()
            l2.backward(); qo.step(); qo.clear_grad()
        np.testing.assert_array_equal(ref.weight.numpy(), m.weight.numpy())

    def test_strategy_flag_composes(self):
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet.meta_optimizers import (
            QuantAllReduceOptimizer, apply_strategy)

        strat = fleet.DistributedStrategy()
        strat.int8_allreduce = True
        paddle.seed(0)
        m = nn.Linear(4, 2)
        opt = apply_strategy(
            optimizer.SGD(learning_rate=0.1, parameters=m.parameters()),
            strat)
        assert isinstance(opt, QuantAllReduceOptimizer)

    def test_mnist_scale_dp_training_converges(self):
        """The acceptance bar: an MNIST-scale DP run with int8 gradient
        all-reduce + error feedback reaches the same train-accuracy
        threshold as exact fp32 sync."""
        from paddle_tpu.parallel.mesh import get_mesh
        from paddle_tpu.vision.datasets import MNIST

        ds = MNIST(mode="train", size=256)
        x = np.asarray(ds.images, np.float32).reshape(len(ds.images), -1)
        x = (x / max(x.max(), 1.0)).astype(np.float32)[:256]
        y = np.asarray(ds.labels, np.int64).reshape(-1)[:256].astype(np.int32)
        parallel.init_mesh(dp=4)
        mesh = get_mesh()
        rng = np.random.RandomState(0)
        p0 = {
            "w1": jnp.asarray(rng.randn(x.shape[1], 32) * 0.05, jnp.float32),
            "b1": jnp.zeros((32,), jnp.float32),
            "w2": jnp.asarray(rng.randn(32, 10) * 0.05, jnp.float32),
            "b2": jnp.zeros((10,), jnp.float32),
        }

        def loss_fn(p, xb, yb):
            h = jnp.tanh(xb @ p["w1"] + p["b1"])
            logits = h @ p["w2"] + p["b2"]
            lse = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.take_along_axis(lse, yb[:, None], axis=1).mean()

        def make_step(quant):
            @functools.partial(
                jax.shard_map, mesh=mesh,
                in_specs=(P(), P("dp"), P("dp"), P("dp")),
                out_specs=(P("dp"), P("dp")),
                axis_names=frozenset({"dp"}), check_vma=False)
            def body(p, xb, yb, res):
                g = jax.grad(loss_fn)(p, xb, yb)
                if quant:
                    out, nres = {}, {}
                    for k in g:
                        out[k], nres[k] = quantized_all_reduce_arrays(
                            g[k], "dp", chunk=64, residual=res[k][0],
                            average=True)
                else:
                    out = {k: jax.lax.pmean(g[k], "dp") for k in g}
                    nres = {k: res[k][0] for k in res}
                return ({k: v[None] for k, v in out.items()},
                        {k: v[None] for k, v in nres.items()})

            return jax.jit(body)

        full_loss = jax.jit(loss_fn)

        def train(quant, steps=60, lr=0.5):
            p = dict(p0)
            res = {k: np.zeros((4,) + v.shape, np.float32)
                   for k, v in p0.items()}
            step = make_step(quant)
            for _ in range(steps):
                g, res = step(p, x, y, res)
                p = {k: p[k] - lr * g[k][0] for k in p}
            h = np.tanh(x @ np.asarray(p["w1"]) + np.asarray(p["b1"]))
            pred = (h @ np.asarray(p["w2"]) + np.asarray(p["b2"])).argmax(1)
            return float(full_loss(p, x, y)), float((pred == y).mean())

        fp_loss, fp_acc = train(False)
        q_loss, q_acc = train(True)
        assert fp_acc >= 0.9, fp_acc      # the baseline itself must learn
        assert q_acc >= 0.9, (q_acc, fp_acc)
        assert q_loss <= fp_loss * 1.3 + 0.05, (q_loss, fp_loss)


# ---------------------------------------------------------------------------
# CI surface
# ---------------------------------------------------------------------------
class TestTooling:
    def test_serve_smoke_quantized_script(self):
        script = (pathlib.Path(__file__).resolve().parent.parent
                  / "scripts" / "serve_smoke.py")
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS",)}
        env.update(PTPU_FORCE_PLATFORM="cpu", PTPU_MONITOR="1",
                   JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, str(script), "--quantize", "int8",
             "--kv-cache-dtype", "int8"],
            env=env, capture_output=True, text=True, timeout=600)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "OK" in proc.stdout
        assert "lowbit metrics:" in proc.stdout

    def test_lowbit_monitor_series(self, model):
        if not monitor.enabled():
            pytest.skip("PTPU_MONITOR disabled")
        monitor.reset()
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 8))
        quantize_for_inference(net, "int4")
        LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=2,
                                      kv_cache_dtype="int8"))
        snap = monitor.snapshot()
        have = {k.split("{")[0] for k in snap}
        for want in ("lowbit/bytes_saved", "lowbit/weight_layers",
                     "lowbit/kv_blocks"):
            assert any(k.startswith(want) for k in have), sorted(have)
