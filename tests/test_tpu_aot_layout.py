"""What the TPU's compiler does with the KV pools, checked without a chip:
the ops that touch a pool are compiled for a described v5e at the two
benchmark configurations' pool shapes, and the optimised HLO may hold no
operation that rewrites a whole pool.  A pool kept as ``[nb, bs, H, D]``
was relaid (`reshape` in, `copy` out: two tilings of the same bytes) around
every ragged kernel call, two fifths of a decode step (PERF.md, PR 27); no
CPU test could see it.  The same holds for the GPT block's stacked weights:
a layer's `qkv_w` was written out and transposed in every layer of every
serving step until the block split the QKV product's result instead of
reshaping it (PR 37).

`test_kernel_compiles_for_the_chip` holds the tree to a rule: a kernel
that does not compile for the chip does not live in the tree.  Interpret
mode on a CPU skips every Mosaic legality rule, and three kernels passed
their CPU tests for five rounds while the v5e compiler refused each of
them (ROADMAP S3, S7; deleted in PR 30).  Every Pallas kernel under
`paddle_tpu/ops/` has a case here, or in the tests above it, at a call
shape a cell or a listed model makes.

The topology is described inside a fixture, never at import, and every
test of this kind lives in this one file (one xdist worker loads libtpu).
Nothing here is a measurement."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import pallas_ops as po
from paddle_tpu.ops import power_retention as pr
from paddle_tpu.ops import ragged_paged_attention as rp
from paddle_tpu.ops.paged_attention import (latent_cache_update_arrays,
                                            paged_cache_update_arrays,
                                            quantized_cache_update_arrays)

# (rows, heads, pool blocks): chat-c16 on GPT-3 1.3B, docqa-c8 on 6.7B
SHAPES = {"gpt3-1.3b": (16, 16, 2048), "gpt3-6.7b": (8, 32, 1024)}
BS, D = 16, 128
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\(?[a-z0-9]+\[.*?) ([\w\-]+)\(")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever libtpu raises here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache
    # but cannot be read back without one
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture
def S(one_chip):
    """(shape, dtype) -> an argument that lives on the described chip."""
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=one_chip)


def _pool_sized(hlo_text, elems):
    """{operation: count} over the instructions whose result (or one
    member of a tuple result) has `elems` elements."""
    found = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        for dims in re.findall(r"\[([0-9,]+)\]", m.group(1)):
            n = 1
            for x in dims.split(","):
                n *= int(x)
            if n == elems:
                op = m.group(2)
                if op == "fusion" and "/scatter" in line:
                    op = "fusion:scatter"
                found[op] = found.get(op, 0) + 1
                break
    return found


def _unfused(hlo_text):
    """`hlo_text` less the bodies of the computations a fusion calls: what
    is left are the instructions that write their result to memory.  A
    layer's slice of a stacked weight INSIDE a product's fusion is a view
    (it is how `fc_in_w[l]` has always been read); the same slice as an
    instruction of its own is a copy of the layer's weight."""
    fused = set(re.findall(r" fusion\(.*?calls=%([\w.\-]+)", hlo_text))
    kept, inside = [], False
    for line in hlo_text.splitlines():
        head = re.match(r"^(?:ENTRY )?%([\w.\-]+) \(", line)
        if head:
            inside = head.group(1) in fused
        if not inside:
            kept.append(line)
    return "\n".join(kept)


# what may yield an array of a weight's size: the argument itself, and the
# compiler's own asynchronous prefetch of it into the chip's fast memory
# (`S(1)`; `slice-start` of a layer or `copy-start` of a small stack, the
# pieces joined by the custom call `ConcatBitcast`), which moves a weight
# once, as it lies, beside the compute
_IN_PLACE = {"parameter", "get-tuple-element", "bitcast", "tuple",
             "slice-start", "slice-done", "copy-start", "copy-done",
             "custom-call"}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_ragged_decode_call_moves_no_pool(S, monkeypatch, name):
    """One layer's fused update + attention at C = 1, pools donated: they
    reach the kernel and leave it aliased, and nothing else of their size
    is computed.  At these widths the call takes the per-head products
    over a tile of four blocks (PR 29): Mosaic accepts the four DMAs into
    row slices `[i*16, (i+1)*16)` of one `[64, H*D]` buffer and the 16 /
    32 unrolled heads' lane slices of it."""
    from paddle_tpu.ops import pallas_ops as po

    b, h, nb = SHAPES[name]
    maxb = 2048 // BS
    monkeypatch.setattr(rp, "_on_tpu", lambda: True)   # the gate asks JAX
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    row = S((b, 1, h, D), jnp.bfloat16)
    pool = S((nb, BS, h * D), jnp.bfloat16)
    compiled = jax.jit(rp.ragged_paged_attention_arrays,
                       donate_argnums=(3, 4)).lower(
        row, row, row, pool, pool, S((b, maxb), jnp.int32),
        S((b,), jnp.int32), S((b,), jnp.int32),
        S((b, 1), jnp.int32)).compile()
    assert po.attention_path_counts() == {
        "ragged_kernel": 1, "ragged_kernel:head_products": 1}
    found = _pool_sized(compiled.as_text(), nb * BS * h * D)
    assert found.pop("custom-call") == 1
    assert set(found) <= {"parameter", "get-tuple-element", "bitcast",
                          "tuple"}, found


@pytest.mark.parametrize("name", sorted(SHAPES))
@pytest.mark.parametrize("quant", [False, True], ids=["fp", "int8"])
def test_cache_write_is_in_place(S, quant, name):
    """A chunk's K rows written into a donated pool from the middle of a
    block: the touched blocks are gathered, merged and scattered back
    whole, in place, and nothing else of the pool's size is computed
    (int8: at block_size 32, its sublane tile, with the scales grown)."""
    _, h, nb = SHAPES[name]
    bs = 32 if quant else BS
    rows = S((1, 1000, h, D), jnp.bfloat16)
    slots = S((1, 1000), jnp.int32)
    if quant:
        compiled = jax.jit(quantized_cache_update_arrays,
                           donate_argnums=(0, 1)).lower(
            S((nb, bs, h * D), jnp.int8), S((nb, h), jnp.float32), rows,
            slots).compile()
    else:
        compiled = jax.jit(paged_cache_update_arrays,
                           donate_argnums=(0,)).lower(
            S((nb, bs, h * D), jnp.bfloat16), rows, slots).compile()
    found = _pool_sized(compiled.as_text(), nb * bs * h * D)
    assert found.get("fusion:scatter") == 1, found    # + its own body
    assert set(found) <= {"parameter", "bitcast", "scatter", "tuple",
                          "fusion:scatter"}, found


@pytest.mark.parametrize("where", ["traced", "closed_over"])
def test_latent_write_keeps_the_rows(S, where):
    """The latent writer at the longctx cell's decode shape, its rows and
    slots arguments (the engine's) or constants of the caller's program:
    the TPU compiler folded the second form's window - constant rows
    sliced at constant offsets - to a broadcast of ZEROS and wrote those
    (the chip, PR 34; the CPU's program is right), until
    `paged_cache_update_arrays` kept closed-over slots behind a barrier.
    No window-sized broadcast may feed the scatter in either form."""
    import numpy as np

    pool = S((12288, 64, 384), jnp.bfloat16)
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randn(64, 1, 320), jnp.bfloat16)
    slots = jnp.asarray((np.arange(64) * 5 * 64 + 7)[:, None], jnp.int32)
    if where == "traced":
        compiled = jax.jit(latent_cache_update_arrays,
                           donate_argnums=(0,)).lower(
            pool, S(rows.shape, rows.dtype),
            S(slots.shape, slots.dtype)).compile()
    else:
        compiled = jax.jit(
            lambda p: latent_cache_update_arrays(p, rows, slots),
            donate_argnums=(0,)).lower(pool).compile()
    text = compiled.as_text()
    window = re.compile(r"= bf16\[64,(?:1,)?64,384\]\S* broadcast\(")
    assert not [ln for ln in text.splitlines() if window.search(ln)]
    found = _pool_sized(text, 12288 * 64 * 384)
    assert found.get("fusion:scatter") == 1, found


# -- PR 28: grouped heads, windows, and the engine's seam ---------------------

AFMOE = dict(hq=48, hkv=8, d=128, bs=64, rows=32, maxb=132)


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
def test_ragged_kernel_compiles_at_the_afmoe_shapes(S, monkeypatch, window):
    """48 query heads over pools of 8 K/V heads (rows 1024 wide), block
    64, 32 rows of up to 8,448 tokens: Mosaic takes it, and the pools
    still reach the kernel and leave it aliased."""
    a = AFMOE
    nb = a["rows"] * (a["maxb"] if window is None else 4096 // a["bs"] + 1)
    monkeypatch.setattr(rp, "_on_tpu", lambda: True)
    pool = S((nb, a["bs"], a["hkv"] * a["d"]), jnp.bfloat16)
    new = S((a["rows"], 1, a["hkv"], a["d"]), jnp.bfloat16)

    def call(*args):
        return rp.ragged_paged_attention_arrays(
            *args, **({} if window is None else {"window": window}))

    compiled = jax.jit(call, donate_argnums=(3, 4)).lower(
        S((a["rows"], 1, a["hq"], a["d"]), jnp.bfloat16), new, new, pool,
        pool, S((a["rows"], a["maxb"]), jnp.int32),
        S((a["rows"],), jnp.int32), S((a["rows"],), jnp.int32),
        S((a["rows"], 1), jnp.int32)).compile()
    found = _pool_sized(compiled.as_text(),
                        nb * a["bs"] * a["hkv"] * a["d"])
    assert found.pop("custom-call") == 1
    assert set(found) <= {"parameter", "get-tuple-element", "bitcast",
                          "tuple"}, found


@pytest.mark.parametrize("window", [None, 4096], ids=["full", "window"])
@pytest.mark.parametrize("seq", [1024, 8192])
def test_flash_forward_compiles_at_the_afmoe_shapes(S, monkeypatch, seq,
                                                    window):
    """The prefill of the longest prompt: K and V of one K/V head whole in
    fast memory, shared by its six query heads; K/V are not repeated."""
    from paddle_tpu.ops import pallas_ops as po

    a = AFMOE
    monkeypatch.setattr(po, "_on_tpu", lambda: True)
    compiled = jax.jit(lambda q, k, v: po.flash_attention_arrays(
        q, k, v, is_causal=True, window=window)).lower(
        S((1, seq, a["hq"], a["d"]), jnp.bfloat16),
        S((1, seq, a["hkv"], a["d"]), jnp.bfloat16),
        S((1, seq, a["hkv"], a["d"]), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert f"bf16[1,{seq},{a['hq']},{a['d']}]" in text


def _gpt_engine_programs(one_chip, monkeypatch, name, prefill_len):
    """The engine's decode program and one prefill program of a 2-layer
    GPT at `name`'s widths and rows, compiled for the described v5e
    -> {program: compiled}.  The weights are shapes from the start (at
    the 6.7B widths two layers and the embedding are 2.4 GB of float32
    zeros under `LazyGuard`)."""
    from paddle_tpu.framework.compat import LazyGuard
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.nn import layer as nn_layer
    from paddle_tpu.ops import pallas_ops as po
    from paddle_tpu.serving import EngineConfig, LLMEngine

    rows, nh, _ = SHAPES[name]
    monkeypatch.setattr(rp, "_on_tpu", lambda: True)
    monkeypatch.setattr(po, "_on_tpu", lambda: True)

    class ShapesOnly:
        def __getattr__(self, attr):
            return getattr(jnp, attr)

        def zeros(self, shape, dtype=None):
            return jax.ShapeDtypeStruct(tuple(shape), jnp.bfloat16)

    with monkeypatch.context() as m, LazyGuard():
        m.setattr(nn_layer, "jnp", ShapesOnly())
        model = GPTForCausalLM(GPTConfig(
            vocab_size=50304, hidden_size=nh * D, num_hidden_layers=2,
            num_attention_heads=nh, intermediate_size=4 * nh * D,
            max_position_embeddings=2048, stacked_blocks=True))
    for layer in model.sublayers(include_self=True):
        layer._dtype = jnp.dtype(jnp.bfloat16)
    eng = LLMEngine(model, EngineConfig(block_size=BS, max_num_seqs=rows))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._param_arrays())
    kv = jax.tree_util.tree_map(on_chip, eng._kv_flat())
    return {
        "jit_ragged_decode": eng._get_ragged_exec(rows, 1).lower(
            params, kv, i32(rows, 1), i32(rows), i32(rows),
            (i32(rows, eng.blocks_per_seq),), (i32(rows, 1),)).compile(),
        f"jit_prefill_{prefill_len}": eng._get_prefill_exec(
            prefill_len).lower(params, kv, i32(1, prefill_len),
                               (i32(1, prefill_len),)).compile()}


def test_gpt_engine_programs_keep_the_parents_operations(one_chip,
                                                         monkeypatch):
    """The engine's decode and prefill programs of a 2-layer GPT at the
    1.3B widths compile, for the described v5e, to a recorded operation
    list (tests/fixtures/gpt_engine_ops_pr27.json): a change that is not
    meant to touch the GPT cells' programs leaves it as it is (the seam
    of ISSUE 28 did).  Recorded from PR 27's tree and again from PR 37's,
    whose QKV product reads `qkv_w[l]` in place
    (`test_gpt_engine_programs_read_the_stacked_weights_in_place`).
    What that moved, PR 27 -> PR 37, decode / prefill_512: `copy` 10 -> 4
    / 12 -> 14 (the two transposing copies of a layer's weight gone; the
    prefill copies its `[1, 512, 6144]` result into q, k and v),
    `fusion` 60 -> 61 / 90 -> 89, `bitcast` 29 -> 22 / 66 -> 61,
    `reshape` 10 -> 4 (decode), `copy-start` and `copy-done` 21 -> 7 /
    6 -> 5, `slice-start` and `slice-done` 4 -> 2 (decode), `custom-call`
    6 -> 5 (decode: a `ConcatBitcast`), `get-tuple-element` 40 -> 38 / 44
    -> 38, `tuple` 17 -> 16 / 18 -> 17, `parameter` 221 -> 222 / 288 ->
    287; every arithmetic opcode and `slice` are as they were."""
    import json

    _, nh, nb = SHAPES["gpt3-1.3b"]
    programs = _gpt_engine_programs(one_chip, monkeypatch, "gpt3-1.3b", 512)
    # the program around the kernel moves no pool (PR 27), with the tile
    # as without it: each layer's K and V pool enters one kernel call
    pools = _pool_sized(programs["jit_ragged_decode"].as_text(),
                        nb * BS * nh * D)
    assert pools.pop("custom-call") == 2, pools
    assert set(pools) <= {"parameter", "get-tuple-element", "bitcast",
                          "tuple"}, pools
    got = {k: _program_ops(c) for k, c in programs.items()}
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "gpt_engine_ops_pr27.json")) as f:
        assert got == json.load(f)


# a prefill length of the cell whose activations have no weight's element
# count (the MLP's `[512, 8192]` at 1.3B and `[1024, 16384]` at 6.7B have
# `out_w[l]`'s)
_PREFILL = {"gpt3-1.3b": 256, "gpt3-6.7b": 768}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_gpt_engine_programs_read_the_stacked_weights_in_place(
        one_chip, monkeypatch, name):
    """No serving program re-lays a stacked weight.  In the engine's decode
    program and a prefill program, at both GPT cells' widths and rows,
    nothing outside a fusion's body yields an array of the element count
    of a layer's `qkv_w`, `out_w`, `fc_in_w` or `fc_out_w`, or of a whole
    stack of them, but the arguments and the compiler's prefetch of them
    (`_IN_PLACE`): each product's fusion reads its `w[l]` as a view.

    `qkv_w` was the exception until PR 37.  The block reshaped the QKV
    product's result to `[.., 3, nh, hd]`; XLA pushed that into the weight,
    and the program wrote every layer's `[H, 3H]` slice out as `[3H, H]`
    (`slice_bitcast_fusion`, a `fusion` here) and transposed it once more
    (`copy`) before the product read it: 24-27% of both GPT cells' device
    time (ledger, PR 36).  With the parent's expression in
    `models/gpt.py::_stacked_block_body` this test fails on those two."""
    _, nh, nb = SHAPES[name]
    H = nh * D
    programs = _gpt_engine_programs(one_chip, monkeypatch, name,
                                    _PREFILL[name])
    for program, compiled in programs.items():
        # at the 6.7B widths a K/V pool has `fc_in_w[l]`'s element count
        text = _unfused(compiled.as_text()).replace(
            f"bf16[{nb},{BS},{H}]", "bf16[pool]")
        for weight, per_layer in [("qkv_w", 3 * H * H), ("out_w", H * H),
                                  ("fc_in_w, fc_out_w", 4 * H * H)]:
            for what, elems in [("layer", per_layer),
                                ("stack", 2 * per_layer)]:
                found = _pool_sized(text, elems)
                assert set(found) <= _IN_PLACE, (program, weight, what,
                                                 found)


def _program_ops(compiled):
    """{HLO opcode: count} of a compiled program."""
    import collections

    found = collections.Counter()
    for line in compiled.as_text().splitlines():
        m = re.match(
            r"^\s*(?:ROOT )?%[\w.\-]+ = .*?[\]\})] ([\w\-]+)\(", line)
        if m:
            found[m.group(1)] += 1
    return dict(found)


def _afmoe_engine_ops(one_chip, monkeypatch):
    """The decode and prefill programs of a 2-layer afmoe engine (a full
    and a sliding layer, the second an expert layer) at the published
    widths (8 of 256 experts held), compiled for the described v5e
    -> {program: {opcode: count}}.  Run over the parent commit's tree it
    wrote tests/fixtures/afmoe_engine_ops_pr31.json."""
    from paddle_tpu.framework.compat import LazyGuard
    from paddle_tpu.models import AfmoeConfig, AfmoeForCausalLM
    from paddle_tpu.ops import pallas_ops as po
    from paddle_tpu.serving import EngineConfig, LLMEngine

    monkeypatch.setattr(rp, "_on_tpu", lambda: True)
    monkeypatch.setattr(po, "_on_tpu", lambda: True)
    with LazyGuard():
        model = AfmoeForCausalLM(AfmoeConfig(
            vocab_size=25024, num_hidden_layers=2, num_dense_layers=1,
            layer_types=["full_attention", "sliding_attention"],
            num_experts=8, router_experts=256))
    model.to(dtype="bfloat16")
    eng = LLMEngine(model, EngineConfig(block_size=64, max_num_seqs=32,
                                        max_model_len=8448, num_blocks=256))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = jax.tree_util.tree_map(on_chip, eng._param_arrays())
    kv = jax.tree_util.tree_map(on_chip, eng._kv_flat())
    table = i32(32, eng.blocks_per_seq)
    return {
        "jit_ragged_decode": _program_ops(eng._get_ragged_exec(32, 1).lower(
            params, kv, i32(32, 1), i32(32), i32(32), (table, table),
            (i32(32, 1), i32(32, 1))).compile()),
        "jit_prefill_1024": _program_ops(eng._get_prefill_exec(1024).lower(
            params, kv, i32(1, 1024),
            (i32(1, 1024), i32(1, 1024))).compile())}


def test_afmoe_engine_programs_keep_the_parents_operations(one_chip,
                                                           monkeypatch):
    """The state group (ISSUE 32) costs the afmoe cell nothing: a model
    with no state layer builds no state group, and its programs compile to
    the operation lists recorded from the parent commit."""
    import json

    got = _afmoe_engine_ops(one_chip, monkeypatch)
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "afmoe_engine_ops_pr31.json")) as f:
        assert got == json.load(f)


def test_state_group_decode_program_donates_pools_and_state(one_chip,
                                                            monkeypatch):
    """The decode program of an engine WITH a state group (lfm2_moe at
    the published widths: a convolution layer, then an attention layer
    over 8 of 64 experts; 64 rows) compiles for the described v5e; every
    K/V pool and state pool goes in donated and comes out aliased; each
    K/V pool enters one kernel call and nothing else of its size is
    computed; a state pool is gathered from and scattered into, staged
    through fast memory (`copy-start` / `copy-done`: it is half a
    megabyte), and never relaid or copied whole by a `copy`."""
    from paddle_tpu.framework.compat import LazyGuard
    from paddle_tpu.models import Lfm2MoeConfig, Lfm2MoeForCausalLM
    from paddle_tpu.ops import pallas_ops as po
    from paddle_tpu.serving import EngineConfig, LLMEngine

    monkeypatch.setattr(rp, "_on_tpu", lambda: True)
    monkeypatch.setattr(po, "_on_tpu", lambda: True)
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    with LazyGuard():
        model = Lfm2MoeForCausalLM(Lfm2MoeConfig(
            vocab_size=8192, num_hidden_layers=2, num_dense_layers=1,
            layer_types=["conv", "full_attention"], num_experts=8,
            router_experts=64))
    model.to(dtype="bfloat16")
    rows = 64
    eng = LLMEngine(model, EngineConfig(block_size=64, max_num_seqs=rows,
                                        max_model_len=4608, num_blocks=500))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    kv = jax.tree_util.tree_map(on_chip, eng._kv_flat())
    state, k_pool, _ = kv
    assert state.shape == (rows + 1, 2, 2048) and k_pool.shape == (
        500, 64, 512)
    compiled = eng._get_ragged_exec(rows, 1).lower(
        jax.tree_util.tree_map(on_chip, eng._param_arrays()), kv,
        i32(rows, 1), i32(rows), i32(rows), (i32(rows, eng.blocks_per_seq),),
        (i32(rows, 1),), (i32(rows),)).compile()
    assert po.attention_path_counts() == {
        "ragged_kernel": 1, "ragged_kernel:head_products": 1}
    text = compiled.as_text()
    header = text.split("\n", 1)[0]
    assert header.count("-alias)") == len(kv), header[:400]
    pools = _pool_sized(text, k_pool.size)
    assert pools.pop("custom-call") == 1, pools    # K and V: one call
    assert set(pools) <= {"parameter", "get-tuple-element", "bitcast",
                          "tuple"}, pools
    states = _pool_sized(text, state.size)
    assert states.get("fusion:scatter") == 1, states
    assert set(states) <= {"parameter", "bitcast", "tuple", "scatter",
                           "fusion:scatter", "copy-start",
                           "copy-done"}, states


def test_all_state_decode_program_updates_each_state_in_its_slot(
        one_chip, monkeypatch):
    """The decode program of an engine with NO K/V group (brumby at the
    published widths, 2 layers, 24 rows over 25 slots of `[8, 65, 136,
    128]` float32: 36.2 MB a slot a layer) compiles for the described
    v5e: it takes no table and no slot array of a K/V pool; both state
    pools go in donated and come out aliased; each enters ONE kernel call
    and nothing else of its size is computed - no copy, no relayout - and
    no array of the ROWS' states (`[24, 8, 65, 136, 128]`) exists at all:
    nothing is gathered out of a pool or scattered into one."""
    from paddle_tpu.framework.compat import LazyGuard
    from paddle_tpu.models import BrumbyConfig, BrumbyForCausalLM
    from paddle_tpu.nn import layer as nn_layer
    from paddle_tpu.serving import EngineConfig, LLMEngine
    from paddle_tpu.serving import kv_cache

    monkeypatch.setattr(po, "_on_tpu", lambda: True)
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()

    class ShapesOnly:       # 3.1 GB of weights and 1.8 GB of pools as shapes
        def __init__(self, dtype=None):
            self.dtype = dtype

        def __getattr__(self, attr):
            return getattr(jnp, attr)

        def zeros(self, shape, dtype=None):
            return jax.ShapeDtypeStruct(tuple(shape),
                                        jnp.dtype(self.dtype or dtype))

    rows = 24
    with monkeypatch.context() as m, LazyGuard():
        m.setattr(nn_layer, "jnp", ShapesOnly(jnp.bfloat16))
        m.setattr(kv_cache, "jnp", ShapesOnly())
        model = BrumbyForCausalLM(BrumbyConfig(num_hidden_layers=2))
        for layer in model.sublayers(include_self=True):
            layer._dtype = jnp.dtype(jnp.bfloat16)
        eng = LLMEngine(model, EngineConfig(
            block_size=64, max_num_seqs=rows, max_model_len=10240))
    assert eng.caches == {} and list(eng.states) == ["retention"]

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    kv = jax.tree_util.tree_map(on_chip, eng._kv_flat())
    assert [(p.shape, p.dtype) for p in kv] == [
        ((rows + 1, 8, 65, 136, 128), jnp.float32)] * 2
    compiled = eng._get_ragged_exec(rows, 1).lower(
        jax.tree_util.tree_map(on_chip, eng._param_arrays()), kv,
        i32(rows, 1), i32(rows), i32(rows), (), (), (i32(rows),)).compile()
    assert po.attention_path_counts() == {"retention_decode_kernel": 2}
    text = compiled.as_text()
    header = text.split("\n", 1)[0]
    assert header.count("-alias)") == len(kv), header[:400]
    pools = _pool_sized(text, kv[0].size)
    assert pools.pop("custom-call") == 2, pools    # a call a layer
    assert set(pools) <= {"parameter", "get-tuple-element", "bitcast",
                          "tuple"}, pools
    assert _pool_sized(text, kv[0].size // (rows + 1) * rows) == {}


# -- PR 30: every kernel that stays compiles for the chip ---------------------

def _flash(**kw):
    return lambda q, k, v, *mask: po.flash_attention_arrays(
        q, k, v, *mask, **kw)


def _grads(fn):
    """`fn`'s forward and backward kernels (flash_fwd, flash_bwd_dq,
    flash_bwd_dkv) in one program."""
    return lambda q, k, v, *rest: jax.grad(
        lambda *qkv: fn(*qkv, *rest).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _ragged(q, kn, vn, kb, vb, tables, pos0, lens, slots, *scales):
    kw = dict(k_scales=scales[0], v_scales=scales[1]) if scales else {}
    return rp.ragged_paged_attention_arrays(
        q, kn, vn, kb, vb, tables, pos0, lens, slots, **kw)


def _latent(q, new, pool, tables, pos0, lens, slots):
    return rp.ragged_latent_attention_arrays(
        q, new, pool, tables, pos0, lens, slots, value_dim=256,
        scale=0.25)


def _retention_decode(q, k, v, log_g, pool, slots):
    return pr.retention_decode(q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], pool,
                               slots)


def _retention_prefill(fresh):
    return lambda *a: pr.retention_prefill(*a, fresh)


def _retention_args(b, t, rows=24, hq=40, hkv=8, d=128):
    return [((b, t, hq, d), jnp.bfloat16), ((b, t, hkv, d), jnp.bfloat16),
            ((b, t, hkv, d), jnp.bfloat16), ((b, t, hkv), jnp.float32),
            ((rows + 1,) + pr.state_shape(hkv, d), jnp.float32)]


def _qkv(b, sq, h, d, sk=None):
    sk = sq if sk is None else sk
    return [((b, sq, h, d), jnp.bfloat16)] + [((b, sk, h, d),
                                               jnp.bfloat16)] * 2


def _decode_args(b, smax, h, d):
    return [((b, 1, h, d), jnp.bfloat16)] + [
        ((b, smax, h * d), jnp.bfloat16)] * 2 + [((), jnp.int32)]


def _ragged_args(b, h, d, nb, bs, pool_dt=jnp.bfloat16):
    """One layer's decode call over pools `[nb, bs, h*d]`, tables sized
    for 2,048 tokens a row; int8 pools bring their `[nb, h]` scales."""
    row = ((b, 1, h, d), jnp.bfloat16)
    pool = ((nb, bs, h * d), pool_dt)
    args = [row, row, row, pool, pool, ((b, 2048 // bs), jnp.int32),
            ((b,), jnp.int32), ((b,), jnp.int32), ((b, 1), jnp.int32)]
    if pool_dt == jnp.int8:
        args += [((nb, h), jnp.float32)] * 2
    return args


def _ragged_args_grouped(b, hq, hkv, d, nb, bs, max_len):
    """One layer's decode call with `hq` query heads over pools of `hkv`
    K/V heads, tables sized for `max_len` tokens a row."""
    new = ((b, 1, hkv, d), jnp.bfloat16)
    pool = ((nb, bs, hkv * d), jnp.bfloat16)
    return [((b, 1, hq, d), jnp.bfloat16), new, new, pool, pool,
            ((b, max_len // bs), jnp.int32), ((b,), jnp.int32),
            ((b,), jnp.int32), ((b, 1), jnp.int32)]


def _case(name, fn, shapes, n_calls, counted=None, **kw):
    """A call, its argument shapes, the Mosaic calls in the compiled
    program, and what the decode kernel's gate counted."""
    return pytest.param(fn, shapes, n_calls, counted or {}, id=name, **kw)


_MASK = [((2, 1, 256, 256), jnp.float32)]
_HEADS = {"ragged_kernel": 1, "ragged_kernel:head_products": 1}
_SEGMENTS = {"ragged_kernel": 1, "ragged_kernel:segment_products": 1}
_LATENTS = {"ragged_kernel": 1, "ragged_kernel:latent_products": 1}
_REFUSED = pytest.mark.xfail(strict=True, reason=(
    "the flash kernels' [B, 1] / [B, S] int32 operand: `block shape ... "
    "divisible by 8 and 128` (ROADMAP S9)"))
KERNEL_CASES = [
    # gpt3-1.3b.pretrain-2k's call and GPT-2 124M's: forward and backward
    _case("flash_fwd_bwd_pretrain2k", _grads(_flash(is_causal=True)),
          _qkv(2, 2048, 16, 128), 3),
    _case("flash_fwd_bwd_h12_d64", _grads(_flash(is_causal=True)),
          _qkv(8, 1024, 12, 64), 3),
    # chat-c16's prefills (H16) at each block geometry the kernel picks
    # for them, and the longest of docqa-c8 (H32)
    _case("flash_fwd_prefill_h16_s128", _flash(is_causal=True),
          _qkv(1, 128, 16, 128), 1),
    _case("flash_fwd_prefill_h16_s256", _flash(is_causal=True),
          _qkv(1, 256, 16, 128), 1),
    _case("flash_fwd_prefill_h16_s1024", _flash(is_causal=True),
          _qkv(1, 1024, 16, 128), 1),
    _case("flash_fwd_prefill_h32_s1536", _flash(is_causal=True),
          _qkv(1, 1536, 32, 128), 1),
    # the kernel's other arguments: an additive mask (forward, and with
    # its backward), keys of another length, causal over a longer cache,
    # a window over ungrouped heads, and the two the compiler refuses
    _case("flash_masked", _flash(), _qkv(2, 256, 16, 128) + _MASK, 1),
    _case("flash_masked_bwd", _grads(_flash()),
          _qkv(2, 256, 16, 128) + _MASK, 3),
    _case("flash_cross", _flash(), _qkv(2, 256, 16, 128, sk=128), 1),
    _case("flash_causal_cross", _flash(is_causal=True),
          _qkv(2, 256, 16, 128, sk=512), 1),
    _case("flash_window", _flash(is_causal=True, window=1024),
          _qkv(1, 4096, 16, 128), 1),
    _case("flash_kv_lens",
          lambda q, k, v, n: po.flash_attention_arrays(q, k, v, kv_lens=n),
          _qkv(2, 2048, 16, 128) + [((2,), jnp.int32)], 1, marks=_REFUSED),
    _case("flash_segment_ids",
          lambda q, k, v, ids: po.flash_attention_arrays(
              q, k, v, is_causal=True, segment_ids=ids),
          _qkv(2, 2048, 16, 128) + [((2, 2048), jnp.int32)], 1,
          marks=_REFUSED),
    # generate()'s dense decode at the listed GPT widths
    _case("flash_decode_h16_d128", lambda *a: po.flash_decode_arrays(*a),
          _decode_args(8, 2048, 16, 128), 1),
    _case("flash_decode_h12_d64", lambda *a: po.flash_decode_arrays(*a),
          _decode_args(8, 1024, 12, 64), 1),
    _case("flash_decode_h32_d128", lambda *a: po.flash_decode_arrays(*a),
          _decode_args(8, 2048, 32, 128), 1),
    # the decode kernel's tile of pool blocks at the block sizes above the
    # cells' 16: two blocks a tile, one, and a block larger than the tile
    _case("ragged_bs32", _ragged, _ragged_args(16, 16, 128, 1024, 32), 1,
          _HEADS),
    _case("ragged_bs64", _ragged, _ragged_args(16, 16, 128, 512, 64), 1,
          _HEADS),
    _case("ragged_bs128", _ragged, _ragged_args(16, 16, 128, 256, 128), 1,
          _HEADS),
    # its segment-indicator body, which no cell runs: int8 pools at the
    # two GPT cells' shapes (block 32, int8's sublane tile), and
    # full-precision heads of 64 lanes
    _case("ragged_int8_gpt3-1.3b", _ragged,
          _ragged_args(16, 16, 128, 1024, 32, jnp.int8), 1, _SEGMENTS),
    _case("ragged_int8_gpt3-6.7b", _ragged,
          _ragged_args(8, 32, 128, 512, 32, jnp.int8), 1, _SEGMENTS),
    _case("ragged_h12_d64", _ragged, _ragged_args(8, 12, 64, 1024, 16), 1,
          _SEGMENTS),
    # lfm2-24b-a2b-l9.agents-c64's calls (PR 32): 64 rows of 32 query heads
    # over 8 K/V heads of 64 lanes, two K/V heads a lane tile of the
    # `[4608, 64, 512]` pools, through the per-head products; and its
    # longest prefill
    _case("ragged_gqa64_lfm2", _ragged,
          _ragged_args_grouped(64, 32, 8, 64, 4608, 64, 4608), 1, _HEADS),
    _case("flash_fwd_prefill_gqa64_s4096", _flash(is_causal=True),
          [((1, 4096, 32, 64), jnp.bfloat16)]
          + [((1, 4096, 8, 64), jnp.bfloat16)] * 2, 1),
    # mistral-small-4-ep8-l8.longctx-c64's calls (PR 34): 64 rows of 32
    # absorbed query heads of 320 over the ONE latent pool `[12288, 64,
    # 384]`, tables for 17,408 tokens a row; and its longest prefill, 32
    # expanded heads of 128 over 16,384 tokens
    _case("ragged_latent_mistral4", _latent,
          [((64, 1, 32, 320), jnp.bfloat16), ((64, 1, 320), jnp.bfloat16),
           ((12288, 64, 384), jnp.bfloat16), ((64, 17408 // 64), jnp.int32),
           ((64,), jnp.int32), ((64,), jnp.int32), ((64, 1), jnp.int32)],
          1, _LATENTS),
    _case("flash_fwd_prefill_h32_s16384", _flash(is_causal=True),
          _qkv(1, 16384, 32, 128), 1),
    # brumby-14b-l6.longgen-c24's calls (PR 38): 24 rows of 40 query heads
    # over 8 K/V heads of 128 against the float32 state pool `[25, 8, 65,
    # 136, 128]`, one position a row; and a prompt's 1,024 positions from
    # a zero state and from the state in the slot
    _case("retention_decode_brumby", _retention_decode,
          _retention_args(24, 1) + [((24,), jnp.int32)], 1),
    _case("retention_prefill_fresh_brumby", _retention_prefill(True),
          _retention_args(1, 1024) + [((1,), jnp.int32)], 1),
    _case("retention_prefill_carried_brumby", _retention_prefill(False),
          _retention_args(1, 1024) + [((1,), jnp.int32)], 1),
]


@pytest.mark.parametrize("fn,shapes,n_calls,counted", KERNEL_CASES)
def test_kernel_compiles_for_the_chip(S, monkeypatch, fn, shapes, n_calls,
                                      counted):
    """Mosaic takes the kernel at this call, and the compiled program
    holds it: the gate chose the kernel and not its XLA fallback."""
    monkeypatch.setattr(po, "_on_tpu", lambda: True)   # the gates ask JAX
    monkeypatch.setattr(rp, "_on_tpu", lambda: True)
    monkeypatch.setenv("PTPU_ATTN_DEBUG", "1")
    po.reset_attention_path_counts()
    text = jax.jit(fn).lower(
        *(S(shape, dt) for shape, dt in shapes)).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == n_calls
    ragged = {k: v for k, v in po.attention_path_counts().items()
              if k.startswith("ragged")}
    assert ragged == counted


@pytest.mark.parametrize("rows,vocab", [(64, 65536), (16, 50304)],
                         ids=["lfm2-agents-c64", "gpt3-chat-c16"])
def test_sample_program_keeps_its_sort_under_the_conditional(S, rows, vocab):
    """The engine's sample program at two cells' shapes: the chip's
    compiler keeps the batch-level switch a `conditional` (it may flatten
    one into selects that run every side), and the only vocabulary sort
    lies in the third branch's computation, which a batch of greedy or
    untruncated rows never enters."""
    from paddle_tpu.serving.engine import _sample_program

    text = jax.jit(_sample_program).lower(
        S((rows, vocab), jnp.float32), S((rows, 2), jnp.uint32),
        S((rows,), jnp.bool_), S((rows,), jnp.float32),
        S((rows,), jnp.int32), S((rows,), jnp.float32)).compile().as_text()
    holder = {}             # opcode -> the computations that hold one
    name = None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?(%[\w.\-]+) \(.*\{$", line)
        if head:
            name = "ENTRY" if head.group(1) else head.group(2)
        m = _INSTR.match(line)
        if m and m.group(2) in ("sort", "conditional"):
            holder.setdefault(m.group(2), []).append((name, line))
    [(where, switch)] = holder["conditional"]
    assert where == "ENTRY"
    branches = re.search(r"branch_computations=\{([^}]*)\}", switch)
    branches = [b.strip() for b in branches.group(1).split(",")]
    assert len(branches) == 3
    [(where, sort)] = holder["sort"]
    assert where == branches[2], (where, branches)
    assert f"f32[{rows},1,{vocab}]" in sort
