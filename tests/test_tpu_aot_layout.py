"""What the TPU's compiler does with the KV pools, checked without a chip:
the ops that touch a pool are compiled for a described v5e at the two
benchmark configurations' pool shapes, and the optimised HLO may hold no
operation that rewrites a whole pool.  A pool kept as ``[nb, bs, H, D]``
was relaid (`reshape` in, `copy` out: two tilings of the same bytes) around
every ragged kernel call, two fifths of a decode step (PERF.md, PR 27); no
CPU test could see it.

The topology is described inside a fixture, never at import, and every
test of this kind lives in this one file (one xdist worker loads libtpu).
Nothing here is a measurement."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops import ragged_paged_attention as rp
from paddle_tpu.ops.paged_attention import (paged_cache_update_arrays,
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


def test_gpt_engine_programs_keep_the_parents_operations(one_chip,
                                                         monkeypatch):
    """The seam (ISSUE 28) costs the GPT cells nothing: the engine's decode
    and prefill programs of a 2-layer GPT at the 1.3B widths compile, for
    the described v5e, to the operation list recorded from the parent
    commit (tests/fixtures/gpt_engine_ops_pr27.json)."""
    import collections
    import json

    from paddle_tpu.framework.compat import LazyGuard
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.ops import pallas_ops as po
    from paddle_tpu.serving import EngineConfig, LLMEngine

    monkeypatch.setattr(rp, "_on_tpu", lambda: True)
    monkeypatch.setattr(po, "_on_tpu", lambda: True)
    with LazyGuard():
        model = GPTForCausalLM(GPTConfig(
            vocab_size=50304, hidden_size=2048, num_hidden_layers=2,
            num_attention_heads=16, intermediate_size=8192,
            max_position_embeddings=2048, stacked_blocks=True))
    model.to(dtype="bfloat16")
    eng = LLMEngine(model, EngineConfig(block_size=16, max_num_seqs=16))

    def on_chip(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    def ops(compiled):
        found = collections.Counter()
        for line in compiled.as_text().splitlines():
            m = re.match(
                r"^\s*(?:ROOT )?%[\w.\-]+ = .*?[\]\})] ([\w\-]+)\(", line)
            if m:
                found[m.group(1)] += 1
        return dict(found)

    params = jax.tree_util.tree_map(on_chip, eng._param_arrays())
    kv = jax.tree_util.tree_map(on_chip, eng._kv_flat())
    decode = eng._get_ragged_exec(16, 1).lower(
        params, kv, i32(16, 1), i32(16), i32(16),
        (i32(16, eng.blocks_per_seq),), (i32(16, 1),)).compile()
    # the program around the kernel moves no pool (PR 27), with the tile
    # as without it: each layer's K and V pool enters one kernel call
    pools = _pool_sized(decode.as_text(), kv[0].size)
    assert pools.pop("custom-call") == 2, pools
    assert set(pools) <= {"parameter", "get-tuple-element", "bitcast",
                          "tuple"}, pools
    got = {
        "jit_ragged_decode": ops(decode),
        "jit_prefill_512": ops(eng._get_prefill_exec(512).lower(
            params, kv, i32(1, 512), (i32(1, 512),)).compile())}
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "gpt_engine_ops_pr27.json")) as f:
        assert got == json.load(f)
