#!/usr/bin/env python
"""On-chip A/B for packed-sequence (segment-id) attention.

Compares, at pretraining-ish shapes:
  kernel_segs   — flash kernel with in-kernel segment masking + block skip
  dense_mask    — XLA softmax with a materialized [B,1,S,S] segment mask
  kernel_causal — flash kernel, causal only (no packing; throughput ceiling)

Prints one JSON line per config. Run on the real chip.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import pallas_ops as po


def seg_ids(doc_len, S, B, seed=0):
    rs = np.random.RandomState(seed)
    out = np.zeros((B, S), np.int32)
    for b in range(B):
        pos, i = 0, 0
        while pos < S:
            ln = int(rs.randint(doc_len // 2, doc_len + 1))
            out[b, pos:pos + ln] = i
            pos += ln
            i += 1
    return jnp.asarray(out)


def timeit(fn, *args, iters=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main():
    B, H, D = 8, 12, 64
    for S, doc in ((1024, 256), (2048, 512), (4096, 512)):
        q = jnp.asarray(np.random.RandomState(1).randn(B, S, H, D),
                        jnp.bfloat16)
        k = jnp.asarray(np.random.RandomState(2).randn(B, S, H, D),
                        jnp.bfloat16)
        v = jnp.asarray(np.random.RandomState(3).randn(B, S, H, D),
                        jnp.bfloat16)
        segs = seg_ids(doc, S, B)

        kernel_segs = jax.jit(lambda q, k, v, s: po.flash_attention_arrays(
            q, k, v, None, True, segment_ids=s))
        dense = jax.jit(lambda q, k, v, s: po.mha_reference(
            q, k, v, None, True, segment_ids=s))
        kernel_causal = jax.jit(lambda q, k, v: po.flash_attention_arrays(
            q, k, v, None, True))

        row = {"config": f"B{B}xS{S}xH{H}xD{D}_doc{doc}"}
        row["kernel_segs_ms"] = timeit(kernel_segs, q, k, v, segs) * 1e3
        try:
            row["dense_mask_ms"] = timeit(dense, q, k, v, segs) * 1e3
        except Exception as e:   # S=4096 dense may OOM — that IS the point
            row["dense_mask_ms"] = f"failed: {type(e).__name__}"
        row["kernel_causal_ms"] = timeit(kernel_causal, q, k, v) * 1e3
        if isinstance(row["dense_mask_ms"], float):
            row["speedup_vs_dense"] = row["dense_mask_ms"] / row["kernel_segs_ms"]
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
