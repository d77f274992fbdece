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
    is computed."""
    b, h, nb = SHAPES[name]
    maxb = 2048 // BS
    monkeypatch.setattr(rp, "_on_tpu", lambda: True)   # the gate asks JAX
    row = S((b, 1, h, D), jnp.bfloat16)
    pool = S((nb, BS, h * D), jnp.bfloat16)
    compiled = jax.jit(rp.ragged_paged_attention_arrays,
                       donate_argnums=(3, 4)).lower(
        row, row, row, pool, pool, S((b, maxb), jnp.int32),
        S((b,), jnp.int32), S((b,), jnp.int32),
        S((b, 1), jnp.int32)).compile()
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
