"""paddle_tpu.lowbit — real int8/int4 low-bit runtime (ISSUE 4 tentpole).

Three wings, one storage convention (`ops/lowbit.py`: symmetric abs-max,
``dequant = codes * scale``):

1. **weight-only quantized inference** (`weight_only.py`) —
   `quantize_for_inference(model, weight_dtype="int8"|"int4")` swaps
   `nn.Linear` → `WeightOnlyLinear` (packed codes + per-channel scales,
   dequant-in-kernel matmul with fp32 accumulate); the quantization kit's
   QAT/PTQ `convert(weight_only=...)` targets it with calibrated scales.
2. **quantized KV cache** (`serving.BlockKVCache(kv_quant="int8")`,
   `LLMEngine(EngineConfig(kv_cache_dtype="int8"))`) — int8 block pools
   with per-block-per-head scales, written by
   `ops/paged_attention.py`'s quantizing pool writer and dequantized at
   the block loads of `ops/ragged_paged_attention.py` (kernel) or folded
   into its XLA fallback; ~halved bytes/block ⇒ ~2× blocks per pool.
3. **quantized collectives** (`comm.py`) — EQuARX-style int8 all-reduce /
   all-gather (shared per-chunk scale, int32 reduction, optional error
   feedback), exposed as `distributed.all_reduce(..., compress="int8")`
   and the fleet ``int8_allreduce`` strategy flag.

Monitor series: ``lowbit/bytes_saved{wing}``, ``lowbit/weight_layers``,
``lowbit/kv_blocks{dtype}``, ``lowbit/comm_bytes{kind,mode}``,
``lowbit/comm_compression_ratio{kind}``, ``lowbit/dequant_calls{site}``.
"""
from .weight_only import WeightOnlyLinear, quantize_for_inference
from .comm import (DEFAULT_CHUNK, quantized_all_gather_arrays,
                   quantized_all_reduce_arrays)
from ..ops.lowbit import (dequantize_arrays, pack_int4_arrays,
                          qmax_for_bits, quantize_absmax_arrays,
                          quantized_matmul_arrays, unpack_int4_arrays)

__all__ = [
    "WeightOnlyLinear", "quantize_for_inference",
    "quantized_all_reduce_arrays", "quantized_all_gather_arrays",
    "DEFAULT_CHUNK",
    "quantize_absmax_arrays", "dequantize_arrays", "quantized_matmul_arrays",
    "pack_int4_arrays", "unpack_int4_arrays", "qmax_for_bits",
]
