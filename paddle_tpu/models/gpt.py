"""GPT decoder family — the flagship pretraining model (BASELINE.md configs
4/5: GPT-3 1.3B DP, GPT-3 6.7B TP+PP+sharding).

Reference analog: the fleet hybrid-parallel GPT built from
fleet/layers/mpu/mp_layers.py (VocabParallelEmbedding / ColumnParallelLinear /
RowParallelLinear / ParallelCrossEntropy) + fused attention
(paddle/fluid/operators/fused/fused_attention_op.cu) + fused FFN
(fused_feedforward_op.cu) + fused_multi_transformer_op.cu.

TPU-native design:
- weights carry mesh-axis annotations ('mp' on hidden/head dims); GSPMD
  inserts the tensor-parallel collectives the reference codes as c_* ops,
- attention is the Pallas flash kernel (ops/pallas_ops.py) — blockwise,
  never materializing the [s, s] score matrix,
- sequence dim of activations is annotated 'sp' (sequence parallel) so
  LN/residual/FFN work is sharded over sequence; attention gathers heads
  instead (Ulysses-style all-to-all, derived by GSPMD from the layout
  switch seq-sharded -> head-sharded),
- everything is bf16-first with fp32 master weights in the optimizer.
"""
from __future__ import annotations

import dataclasses
import os
from functools import partial as functools_partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core.dispatch import apply
from ..nn.layer import Layer
from ..nn import functional as F
from ..nn.initializer import Normal, Constant
from ..nn.norm import LayerNorm
from ..nn.common import Linear, Dropout, Embedding
from ..ops.pallas_ops import cached_attention_arrays, flash_attention
from .serving_form import LayerSpec, ServingForm
from ..parallel import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy, constraint, shard_parameter,
)

__all__ = [
    "GPTConfig", "GPTModel", "GPTForCausalLM", "GPTPretrainingCriterion",
    "GPTStackedBlocks", "gpt_test_config", "gpt2_124m_config",
    "gpt3_1p3b_config", "gpt3_6p7b_config",
]


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.0
    attention_dropout_prob: float = 0.0
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    use_flash_attention: bool = True
    # sequence-parallel activation annotation (no-op when sp axis is 1)
    sequence_parallel: bool = True
    # context parallelism: keep the sequence sharded over 'sp' THROUGH
    # attention via ring attention (parallel/ring.py) instead of gathering
    # to full-sequence flash attention. The long-context path.
    context_parallel: bool = False
    # "zigzag" load-balances the causal ring: the MODEL permutes the token
    # stream once after the embedding (zigzag_sequence_perm) and
    # un-permutes before the final LN, so every sp rank does identical
    # attention work (the contiguous ring leaves rank n-1 computing n full
    # blocks while rank 0 masks all but one). Needs attention_dropout 0,
    # pp degenerate, and seq % (2*sp) == 0.
    cp_layout: str = "contiguous"
    # MoE: replace the dense FFN with a mixture of experts every n blocks
    moe_every_n: int = 0
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    # stacked blocks: one [L, ...] weight per tensor, scan/pipeline executed
    # (enables pp>1; also O(1)-in-depth compile time)
    stacked_blocks: bool = False
    pp_num_microbatches: int = 0  # 0 -> pp degree
    # pipeline schedule under pp>1: "gpipe" (autodiff-transparent forward,
    # parallel/pipeline.py:pipeline_apply) or "1f1b" (fused fwd+bwd with
    # bounded activation stashes, pipeline_1f1b — reference
    # meta_parallel/pipeline_parallel.py:230). "1f1b" takes effect in
    # pretrain_loss(); plain forward() always uses gpipe.
    pp_schedule: str = "gpipe"
    # virtual chunks per pipeline stage (>1 = interleaved schedule,
    # reference PipelineParallelWithInterleave :461; shrinks the bubble
    # v-fold). Applies to the gpipe forward path.
    pp_num_chunks: int = 1
    # activation recompute per block (reference fleet/recompute; here
    # jax.checkpoint around the stacked block body, so backward re-runs
    # each block's forward instead of stashing its internals — the
    # standard memory/FLOPs trade for pipeline/large configs)
    recompute: bool = False


def gpt_test_config(**kw):
    """Tiny config for tests/dryruns."""
    d = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
             num_attention_heads=4, intermediate_size=128,
             max_position_embeddings=64, sequence_parallel=True)
    d.update(kw)
    return GPTConfig(**d)


def gpt2_124m_config(**kw):
    d = dict(vocab_size=50304, hidden_size=768, num_hidden_layers=12,
             num_attention_heads=12, intermediate_size=3072,
             max_position_embeddings=1024)
    d.update(kw)
    return GPTConfig(**d)


def gpt3_1p3b_config(**kw):
    d = dict(vocab_size=50304, hidden_size=2048, num_hidden_layers=24,
             num_attention_heads=16, intermediate_size=8192,
             max_position_embeddings=2048)
    d.update(kw)
    return GPTConfig(**d)


def gpt3_6p7b_config(**kw):
    d = dict(vocab_size=50304, hidden_size=4096, num_hidden_layers=32,
             num_attention_heads=32, intermediate_size=16384,
             max_position_embeddings=2048)
    d.update(kw)
    return GPTConfig(**d)


def _act_spec(cfg, ndim=3):
    """Activation sharding spec [batch, seq, hidden...]: dp on batch, sp on
    sequence when enabled."""
    seq_axis = "sp" if cfg.sequence_parallel else None
    return ["dp", seq_axis] + [None] * (ndim - 2)


class GPTEmbeddings(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
        )
        init = Normal(std=cfg.initializer_range)
        self.word_embeddings.weight.set_value(
            init(self.word_embeddings.weight.shape, "float32")
        )
        self.position_embeddings = Embedding(
            cfg.max_position_embeddings, cfg.hidden_size,
        )
        self.position_embeddings.weight.set_value(
            init(self.position_embeddings.weight.shape, "float32")
        )
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        x = self.word_embeddings(input_ids)
        if position_ids is None:
            seq = input_ids.shape[-1]
            position_ids = Tensor(jnp.arange(seq, dtype=jnp.int32))
        x = x + self.position_embeddings(position_ids)
        x = self.dropout(x)
        return constraint(x, _act_spec(self.cfg))


class GPTAttention(Layer):
    """Fused causal self-attention (reference: fused_attention_op.cu +
    mp_layers QKV column-parallel / out-proj row-parallel split)."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_attention_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        self.qkv_proj = ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, gather_output=False,
        )
        self.out_proj = RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, input_is_parallel=True,
        )
        self.attn_drop = cfg.attention_dropout_prob
        if cfg.context_parallel and cfg.attention_dropout_prob:
            import warnings

            warnings.warn(
                "context_parallel falls back to full-sequence attention while "
                "attention dropout is active in training mode — long-context "
                "memory savings are lost. Set attention_dropout_prob=0 to keep "
                "the ring path.",
                stacklevel=3,
            )

    def forward(self, x, cache=None, time_step=None):
        from ..parallel.mesh import axis_size
        from ..parallel.ring import ring_attention

        b, s, h = x.shape
        qkv = self.qkv_proj(x)                       # [b, s, 3h] mp-sharded last dim
        qkv = qkv.reshape([b, s, 3, self.num_heads, self.head_dim])
        if cache is not None:
            # KV-cache prefill/decode (reference CacheKV semantics:
            # fused_multi_transformer_op.cu:90): write this chunk at
            # position `time_step`, attend causally over the cache.
            # time_step None is STATIC prefill-at-0: causal attention over
            # the chunk (flash path) + cache write, no S_max-wide mask.
            qkv = constraint(qkv, ["dp", None, None, "mp", None])
            q, k, v = qkv.unbind(axis=2)
            k_cache, v_cache = cache
            if time_step is None:
                def prefill_fn(qa, ka, va, kca, vca):
                    return _cached_attn_arrays(qa, ka, va, kca, vca, 0, True)

                o, kc, vc = apply(prefill_fn, q, k, v, k_cache, v_cache,
                                  name="cached_attention_prefill")
            else:
                o, kc, vc = apply(
                    cached_attention_arrays, q, k, v, k_cache, v_cache,
                    time_step, name="cached_attention",
                )
            o = constraint(o, ["dp", None, "mp", None])
            o = o.reshape([b, s, h])
            return self.out_proj(o), (kc, vc)
        use_ring = (
            self.cfg.context_parallel
            and axis_size("sp") > 1
            and not (self.attn_drop and self.training)
        )
        if use_ring:
            # context parallel: seq stays sharded over sp through attention
            qkv = constraint(qkv, ["dp", "sp", None, "mp", None])
            q, k, v = qkv.unbind(axis=2)
            layout = "zigzag_pre" if _zigzag_active(self.cfg) else "contiguous"
            o = ring_attention(q, k, v, is_causal=True, layout=layout)
            o = constraint(o, ["dp", "sp", "mp", None])
        else:
            # heads carry the mp shard; seq gathers (sp -> heads layout switch)
            qkv = constraint(qkv, ["dp", None, None, "mp", None])
            q, k, v = qkv.unbind(axis=2)
            o = flash_attention(
                q, k, v, is_causal=True,
                dropout_p=self.attn_drop, training=self.training,
            )                                        # [b, s, heads, dim]
            o = constraint(o, ["dp", None, "mp", None])
        o = o.reshape([b, s, h])
        return self.out_proj(o)


class GPTMLP(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.fc_in = ColumnParallelLinear(
            cfg.hidden_size, cfg.intermediate_size, gather_output=False,
        )
        self.fc_out = RowParallelLinear(
            cfg.intermediate_size, cfg.hidden_size, input_is_parallel=True,
        )

    def forward(self, x):
        return self.fc_out(F.gelu(self.fc_in(x), approximate=True))


class GPTMoEMLP(Layer):
    """Mixture-of-experts FFN (reference:
    incubate/distributed/models/moe/moe_layer.py:260 — gate -> global_scatter
    alltoall -> experts -> global_gather; collective ops
    global_scatter_op.cu.cc / global_gather_op.cu.cc).

    TPU-native: top-k capacity-factor routing with one-hot dispatch/combine
    einsums; under ep>1 the token batch is sharded over 'ep' in shard_map
    and the dispatch/return are ONE lax.all_to_all each (parallel/moe.py).
    Per-token expert FLOPs are k*cf*H*M — independent of num_experts.
    The GShard load-balance aux loss of the last forward is exposed as
    `self.aux_loss`.
    """

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.num_experts = cfg.moe_num_experts
        self.top_k = cfg.moe_top_k
        self.capacity_factor = cfg.moe_capacity_factor
        h, m = cfg.hidden_size, cfg.intermediate_size
        self.gate = Linear(h, self.num_experts)
        self.w_in = self.create_parameter(
            shape=[self.num_experts, h, m],
            default_initializer=Normal(std=cfg.initializer_range),
        )
        self.w_out = self.create_parameter(
            shape=[self.num_experts, m, h],
            default_initializer=Normal(std=cfg.initializer_range),
        )
        shard_parameter(self.w_in, ("ep", None, "mp"))
        shard_parameter(self.w_out, ("ep", "mp", None))
        self.aux_loss = None

    def forward(self, x):
        from ..parallel.moe import moe_mlp_arrays

        logits = self.gate(x)                        # [b, s, E]
        out, aux = apply(
            functools_partial(moe_mlp_arrays, top_k=self.top_k,
                              capacity_factor=self.capacity_factor),
            x, logits, self.w_in, self.w_out, name="moe_mlp",
        )
        self.aux_loss = aux
        return out


def _cached_attn_arrays(q, k, v, kc, vc, t, prefill, cache_mask=None):
    """Array-level prefill/decode cached-attention dispatch — the single
    source of truth for every cached forward path (per-layer GPTAttention,
    the stacked scan, and the unrolled decode). At STATIC prefill
    (time_step is None → position 0) the cache beyond the chunk is empty,
    so causal flash attention over the chunk plus the cache write is exact
    and skips the O(S * S_max) masked path; decode defers to
    cached_attention_arrays (reference CacheKV semantics:
    fused_multi_transformer_op.cu:90).

    cache_mask: optional additive [B, 1, 1, S_max] over CACHE positions
    (padded-prompt batches: -inf at a row's pad slots) — applied at
    prefill over the chunk's keys and at every decode step."""
    if prefill:
        from ..ops.pallas_ops import flash_attention_arrays

        kw, vw = k, v
        if kc.ndim == 3:                # flat [B, Smax, H*D] cache ring
            b, s = k.shape[0], k.shape[1]
            kw = k.reshape(b, s, -1)
            vw = v.reshape(b, s, -1)
        origin = (0,) * kc.ndim
        kc2 = jax.lax.dynamic_update_slice(kc, kw.astype(kc.dtype), origin)
        vc2 = jax.lax.dynamic_update_slice(vc, vw.astype(vc.dtype), origin)
        m = None
        if cache_mask is not None:
            sq = q.shape[1]
            # broadcast the key-validity row over queries so the flash
            # kernel's [B, 1, Sq, Sk] mask shape contract holds
            m = jnp.broadcast_to(cache_mask[:, :, :, :sq],
                                 (q.shape[0], 1, sq, sq))
        return flash_attention_arrays(q, k, v, m, is_causal=True), kc2, vc2
    return cached_attention_arrays(q, k, v, kc, vc, t, mask=cache_mask)


def _stacked_ln(h, w, b, eps):
    """fp32-accumulated LayerNorm on stacked-block activations."""
    h32 = h.astype(jnp.float32)
    mu = h32.mean(-1, keepdims=True)
    var = ((h32 - mu) ** 2).mean(-1, keepdims=True)
    return ((h32 - mu) * jax.lax.rsqrt(var + eps)).astype(h.dtype) * w + b


def _stacked_mlp(p, h, eps):
    """The MLP half of a stacked block (ln2 -> gelu(fc_in) -> fc_out ->
    residual)."""
    hn = _stacked_ln(h, p["ln2_w"], p["ln2_b"], eps)
    m = jax.nn.gelu(hn @ p["fc_in_w"] + p["fc_in_b"], approximate=True)
    return h + m @ p["fc_out_w"] + p["fc_out_b"]


def _split_heads(qkv, nh, hd):
    """[B, S, 3*nh*hd] QKV product -> q, k, v, each [B, S, nh, hd]: column
    `i*H + n*hd + d` is q/k/v `i`, head `n`, lane `d`.  The RESULT is
    split, never reshaped to [.., 3, nh, hd]: XLA pushes that reshape
    into the product's weight, and a stacked `qkv_w[l]` is then sliced
    out and transposed as a copy in every layer of every step (a
    quarter of a serving step's device time; PERF.md, PR 37)."""
    mb, s, _ = qkv.shape
    return tuple(t.reshape(mb, s, nh, hd)
                 for t in jnp.split(qkv, 3, axis=-1))


def _stacked_block_body(p, h, attn_fn, nh, hd, eps):
    """One pre-LN transformer block over a stacked-weight slice `p`.
    attn_fn: (q, k, v) [B,S,nh,hd] -> (o, extra); `extra` threads cache
    state for the decode path (None in training). Single source of truth
    for the block arithmetic of both GPTStackedBlocks.forward and
    .forward_cached."""
    mb, s, H = h.shape
    hn = _stacked_ln(h, p["ln1_w"], p["ln1_b"], eps)
    q, k, v = _split_heads(hn @ p["qkv_w"] + p["qkv_b"], nh, hd)
    o, extra = attn_fn(q, k, v)
    h = h + o.reshape(mb, s, H) @ p["out_w"] + p["out_b"]
    return _stacked_mlp(p, h, eps), extra


class GPTStackedBlocks(Layer):
    """All L transformer blocks as stacked [L, ...] weights, executed by
    lax.scan (pp=1) or the GPipe collective-permute pipeline (pp>1) — see
    parallel/pipeline.py. The TPU-native form of the reference's
    PipelineLayer segmentation (pp_layers.py:209): stage assignment is the
    'pp' shard of the leading dim, not host-side LayerDesc partitioning."""

    PARAM_AXES = {
        "ln1_w": ("pp", None), "ln1_b": ("pp", None),
        "qkv_w": ("pp", None, "mp"), "qkv_b": ("pp", "mp"),
        "out_w": ("pp", "mp", None), "out_b": ("pp", None),
        "ln2_w": ("pp", None), "ln2_b": ("pp", None),
        "fc_in_w": ("pp", None, "mp"), "fc_in_b": ("pp", "mp"),
        "fc_out_w": ("pp", "mp", None), "fc_out_b": ("pp", None),
    }

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        if cfg.hidden_dropout_prob or cfg.attention_dropout_prob:
            raise ValueError("stacked_blocks path does not support dropout yet")
        if cfg.moe_every_n > 0:
            raise ValueError(
                "stacked_blocks path does not support MoE; use stacked_blocks=False"
            )
        self.cfg = cfg
        L, H, I = cfg.num_hidden_layers, cfg.hidden_size, cfg.intermediate_size
        init = Normal(std=cfg.initializer_range)
        shapes = {
            "ln1_w": [L, H], "ln1_b": [L, H],
            "qkv_w": [L, H, 3 * H], "qkv_b": [L, 3 * H],
            "out_w": [L, H, H], "out_b": [L, H],
            "ln2_w": [L, H], "ln2_b": [L, H],
            "fc_in_w": [L, H, I], "fc_in_b": [L, I],
            "fc_out_w": [L, I, H], "fc_out_b": [L, H],
        }
        for name, shape in shapes.items():
            if name.endswith("_b") or name.startswith("ln"):
                fill = 1.0 if name in ("ln1_w", "ln2_w") else 0.0
                p = self.create_parameter(
                    shape=shape, default_initializer=Constant(fill)
                )
            else:
                p = self.create_parameter(shape=shape, default_initializer=init)
            shard_parameter(p, self.PARAM_AXES[name])
            setattr(self, name, p)
        self._names = list(shapes)

    def block_closure(self, seg_as_arg=False):
        """Array-level single-block function `block(params_slice, h) -> h`
        shared by the gpipe forward, the 1F1B fused loss, and dryruns.
        seg_as_arg=True instead returns `block(params_slice, h, seg) -> h`
        taking packed-sequence segment-id rows as a third argument —
        documents attend only within their own segment (flash kernel
        path; ops/pallas_ops.flash_attention_arrays) and the pipeline
        schedules feed the ids through as per-micro-batch metadata (the
        rows split with the activation micro-batches;
        parallel/pipeline.py `aux`)."""
        from ..parallel.mesh import axis_size
        from ..parallel.ring import ring_attention_arrays
        from ..ops.pallas_ops import flash_attention_arrays

        cfg = self.cfg
        nh, hd = cfg.num_attention_heads, cfg.hidden_size // cfg.num_attention_heads
        eps = cfg.layer_norm_epsilon
        # ring attention composes with the pp shard_map only when pp is
        # degenerate (nested manual axes); pipeline stages fall back to
        # full-sequence flash attention.
        use_ring = (
            cfg.context_parallel and axis_size("sp") > 1 and axis_size("pp") <= 1
        )

        if use_ring and _zigzag_active(cfg):
            from functools import partial as _partial

            # segment ids arrive already zigzag-permuted with the token
            # stream (GPTModel.forward permutes both with one gather)
            attn = _partial(ring_attention_arrays, layout="zigzag_pre")
        elif use_ring:
            attn = ring_attention_arrays
        else:
            attn = flash_attention_arrays

        if seg_as_arg:
            def block(p, h, seg):
                out, _ = _stacked_block_body(
                    p, h, lambda q, k, v: (attn(
                        q, k, v, is_causal=True, segment_ids=seg), None),
                    nh, hd, eps)
                return out
        else:
            def block(p, h):
                out, _ = _stacked_block_body(
                    p, h,
                    lambda q, k, v: (attn(q, k, v, is_causal=True), None),
                    nh, hd, eps)
                return out

        if cfg.recompute:
            # reference fleet/recompute capability on the stacked path:
            # backward re-runs each block instead of stashing internals,
            # bounding activation memory at O(L x residual)
            block = jax.checkpoint(block)
        return block

    def forward(self, x, segment_ids=None):
        from ..parallel.pipeline import pipeline_apply

        names = self._names
        n_micro = self.cfg.pp_num_microbatches or None
        chunks = max(1, self.cfg.pp_num_chunks)

        if segment_ids is not None:
            # ids ride the pipeline as per-micro-batch aux metadata: they
            # split with the activations and every stage reads the rows of
            # the micro-batch it is computing (parallel/pipeline.py aux) —
            # works across gpipe, interleave, and pp=1 scan uniformly
            block = self.block_closure(seg_as_arg=True)

            def fn(a, segs, *flat):
                params = dict(zip(names, flat))
                return pipeline_apply(block, params, a,
                                      n_microbatches=n_micro,
                                      num_chunks=chunks, aux=segs)

            tensors = [getattr(self, n) for n in names]
            return apply(fn, x, segment_ids, *tensors,
                         name="gpt_stacked_blocks")

        block = self.block_closure()

        def fn(a, *flat):
            params = dict(zip(names, flat))
            return pipeline_apply(block, params, a, n_microbatches=n_micro,
                                  num_chunks=chunks)

        tensors = [getattr(self, n) for n in names]
        return apply(fn, x, *tensors, name="gpt_stacked_blocks")

    def forward_cached(self, x, caches, time_step=None, cache_mask=None):
        """KV-cache prefill/decode over the stacked weights.

        Two cache formats select two execution strategies:
        - list of per-layer (k, v) pairs (flat [B,Smax,H*D] each) → UNROLLED
          python loop with static weight slices. This is the fast decode
          path: caches stay separate buffers in the caller's while-loop
          carry so each step's update is an in-place one-row
          dynamic_update_slice, and static `w[l]` slices fuse into their
          matmuls. The scan form instead re-materializes every layer's
          cache slice per step (profiled at ~4x the whole weight-stream
          cost per decode step on v5e).
        - stacked (k [L,B,Smax,H*D], v [L,...]) → lax.scan over the layer
          dim with cache slices as scan xs/ys (one executable regardless
          of depth; the right trade for very deep models).
        """
        stacked_format = (len(caches) == 2 and hasattr(caches[0], "shape")
                          and len(caches[0].shape) in (4, 5))
        if not stacked_format:
            return self._forward_cached_unrolled(x, caches, time_step,
                                                 cache_mask)
        if cache_mask is not None:
            raise NotImplementedError(
                "padded-prompt cache_mask on the stacked layer-scan decode "
                "path is not wired yet; use the unrolled per-layer caches "
                "(the default for <= 32 layers)")
        cfg = self.cfg
        nh = cfg.num_attention_heads
        hd = cfg.hidden_size // nh
        eps = cfg.layer_norm_epsilon
        names = self._names
        k_caches, v_caches = caches

        # time_step is None STATICALLY means prefill at position 0: the
        # cache beyond the chunk is empty, so causal flash attention over
        # the chunk equals cached attention — skip the O(S * S_max)
        # masked path and just write the cache (flash kernel on TPU)
        prefill = time_step is None

        def fn(a, kcs, vcs, t, *flat):
            params = dict(zip(names, flat))

            def body(h, xs):
                p, kc, vc = xs

                def attn_fn(q, k, v):
                    o, kc2, vc2 = _cached_attn_arrays(q, k, v, kc, vc, t,
                                                      prefill)
                    return o, (kc2, vc2)

                h, (kc, vc) = _stacked_block_body(p, h, attn_fn, nh, hd, eps)
                return h, (kc, vc)

            h, (kcs, vcs) = jax.lax.scan(body, a, (params, kcs, vcs))
            return h, kcs, vcs

        tensors = [getattr(self, n) for n in names]
        t = 0 if time_step is None else time_step
        h, kcs, vcs = apply(fn, x, k_caches, v_caches, t, *tensors,
                            name="gpt_stacked_blocks_cached")
        return h, (kcs, vcs)

    def _forward_cached_unrolled(self, x, caches, time_step=None,
                                 cache_mask=None):
        """Unrolled cached forward over per-layer (k, v) cache pairs —
        see forward_cached for why this beats the scan at decode."""
        cfg = self.cfg
        nh = cfg.num_attention_heads
        hd = cfg.hidden_size // nh
        eps = cfg.layer_norm_epsilon
        names = self._names
        L = cfg.num_hidden_layers
        prefill = time_step is None
        has_cm = cache_mask is not None

        def fn(a, t, *flat):
            if has_cm:
                cm, flat = flat[0], flat[1:]
            else:
                cm = None
            cache_flat, params_flat = flat[:2 * L], flat[2 * L:]
            params = dict(zip(names, params_flat))
            h = a
            outs = []
            for l in range(L):
                kc, vc = cache_flat[2 * l], cache_flat[2 * l + 1]
                p = {n: params[n][l] for n in names}

                def attn_fn(q, k, v, kc=kc, vc=vc):
                    o, kc2, vc2 = _cached_attn_arrays(q, k, v, kc, vc, t,
                                                      prefill,
                                                      cache_mask=cm)
                    return o, (kc2, vc2)

                h, (kc2, vc2) = _stacked_block_body(p, h, attn_fn, nh, hd, eps)
                outs += [kc2, vc2]
            return (h, *outs)

        flat_caches = [arr for (kc, vc) in caches for arr in (kc, vc)]
        tensors = [getattr(self, n) for n in names]
        t = 0 if time_step is None else time_step
        mask_args = [cache_mask] if has_cm else []
        res = apply(fn, x, t, *mask_args, *flat_caches, *tensors,
                    name="gpt_stacked_blocks_cached_unrolled")
        h, rest = res[0], res[1:]
        return h, [(rest[2 * l], rest[2 * l + 1]) for l in range(L)]


class GPTBlock(Layer):
    def __init__(self, cfg: GPTConfig, layer_idx: int = 0):
        super().__init__()
        self.cfg = cfg
        self.ln_1 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln_2 = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        use_moe = (
            cfg.moe_every_n > 0
            and cfg.moe_num_experts > 1
            and (layer_idx + 1) % cfg.moe_every_n == 0
        )
        self.mlp = GPTMoEMLP(cfg) if use_moe else GPTMLP(cfg)
        self.dropout = Dropout(cfg.hidden_dropout_prob)

    def forward(self, x, cache=None, time_step=None):
        spec = _act_spec(self.cfg)
        if cache is not None:
            a, new_cache = self.attn(
                self.ln_1(constraint(x, spec)), cache=cache, time_step=time_step)
            x = x + self.dropout(a)
            x = x + self.dropout(self.mlp(self.ln_2(constraint(x, spec))))
            return constraint(x, spec), new_cache
        x = x + self.dropout(self.attn(self.ln_1(constraint(x, spec))))
        x = x + self.dropout(self.mlp(self.ln_2(constraint(x, spec))))
        return constraint(x, spec)


class GPTModel(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.embeddings = GPTEmbeddings(cfg)
        if cfg.stacked_blocks:
            self.blocks = GPTStackedBlocks(cfg)
            self.h = []
        else:
            self.h = [GPTBlock(cfg, i) for i in range(cfg.num_hidden_layers)]
            for i, blk in enumerate(self.h):
                self.add_sublayer(f"h_{i}", blk)
        self.ln_f = LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, caches=None,
                time_step=None, segment_ids=None, cache_mask=None):
        """segment_ids: optional [B, S] packed-sequence ids (stacked-blocks
        training path; see GPTStackedBlocks.block_closure). For packed
        batches also pass position_ids that restart at each document
        boundary — the standard packed pretraining format.
        cache_mask: optional additive [B, 1, 1, S_max] over cache
        positions for padded-prompt decoding (see generate(pad_token_id))."""
        if segment_ids is not None and (caches is not None
                                        or not self.cfg.stacked_blocks):
            raise NotImplementedError(
                "segment_ids are supported on the stacked-blocks training "
                "path (no KV-cache decode); packed decoding is not a "
                "standard inference shape")
        if caches is not None and position_ids is None:
            # decode positions are absolute: time_step + [0, s)
            s = input_ids.shape[-1]
            t = 0 if time_step is None else time_step
            base = t._data if isinstance(t, Tensor) else jnp.asarray(t, jnp.int32)
            position_ids = Tensor(base + jnp.arange(s, dtype=jnp.int32))
        x = self.embeddings(input_ids, position_ids)
        if caches is not None:
            if self.cfg.stacked_blocks:
                x, new_caches = self.blocks.forward_cached(
                    x, caches, time_step, cache_mask=cache_mask)
            else:
                if cache_mask is not None:
                    raise NotImplementedError(
                        "padded-prompt cache_mask is wired on the "
                        "stacked-blocks path; use stacked_blocks=True")
                new_caches = []
                for blk, cache in zip(self.h, caches):
                    x, c = blk(x, cache=cache, time_step=time_step)
                    new_caches.append(c)
            return self.ln_f(x), new_caches
        zig = _zigzag_active(self.cfg)
        if zig:
            from ..parallel.mesh import axis_size
            from ..parallel.ring import zigzag_sequence_perm

            n = axis_size("sp")
            s_len = x.shape[1]
            if s_len % (2 * n) != 0:
                raise ValueError(
                    f"cp_layout='zigzag' needs seq len ({s_len}) divisible "
                    f"by 2*sp ({2 * n}); pad the sequence or use "
                    "cp_layout='contiguous'")
            perm, inv = zigzag_sequence_perm(s_len, n)
            # ONE gather in, one out per step — per-token layers (LN, MLP,
            # residual) are permutation-invariant; attention runs the
            # zigzag_pre kernel whose position bookkeeping matches this
            # exact ordering
            x = apply(lambda a: jnp.take(a, jnp.asarray(perm), axis=1), x,
                      name="zigzag_permute")
        if self.cfg.stacked_blocks:
            seg_arr = None
            if segment_ids is not None:
                seg_arr = (segment_ids._data if isinstance(segment_ids, Tensor)
                           else jnp.asarray(segment_ids))
                seg_arr = jnp.asarray(seg_arr, jnp.int32)
                if zig:
                    # ids follow the token stream into zigzag order (the
                    # zigzag_pre ring expects them pre-permuted)
                    seg_arr = jnp.take(seg_arr, jnp.asarray(perm), axis=1)
            x = self.blocks(x, segment_ids=seg_arr)
        else:
            for blk in self.h:
                x = blk(x)
        if zig:
            x = apply(lambda a: jnp.take(a, jnp.asarray(inv), axis=1), x,
                      name="zigzag_unpermute")
        return self.ln_f(x)


class GPTServingForm(ServingForm):
    """The stacked-blocks GPT as `LLMEngine` serves it: the dense path's
    own arithmetic (same embedding takes, `_stacked_block_body`,
    `F.layer_norm` float32 statistics, the tied head's einsum), so a paged
    decode returns the tokens of `generate()`.  One cache group, full
    attention, as many K/V heads as query heads."""

    def __init__(self, model):
        cfg = model.cfg
        if not cfg.stacked_blocks:
            raise ValueError(
                "LLMEngine serves the stacked-blocks GPT form "
                "(GPTConfig(stacked_blocks=True)) — per-layer Layer "
                "modules would re-trace one program per layer")
        self.gpt, self.cfg = model.gpt, cfg
        self.vocab_size = cfg.vocab_size
        self.max_position_embeddings = cfg.max_position_embeddings
        nh = cfg.num_attention_heads
        self.layer_specs = [LayerSpec(nh, nh, cfg.hidden_size // nh, None,
                                      "full")] * cfg.num_hidden_layers
        self._stack_names = list(model.gpt.blocks._names)

    @property
    def dtype(self):
        return self.gpt.embeddings.word_embeddings.weight.dtype

    def params(self):
        gpt = self.gpt
        params = {n: getattr(gpt.blocks, n)._data for n in self._stack_names}
        params["wte"] = gpt.embeddings.word_embeddings.weight._data
        params["wpe"] = gpt.embeddings.position_embeddings.weight._data
        params["lnf_w"] = gpt.ln_f.weight._data
        params["lnf_b"] = gpt.ln_f.bias._data
        return params

    def embed(self, params, ids, pos):
        return jnp.take(params["wte"], ids, axis=0) \
            + jnp.take(params["wpe"], pos, axis=0)

    def layer(self, l, params, h, pos, attn_fn, valid=None):
        spec = self.layer_specs[l]
        p = {n: params[n][l] for n in self._stack_names}
        h, extra = _stacked_block_body(p, h, attn_fn, spec.num_heads,
                                       spec.head_dim,
                                       self.cfg.layer_norm_epsilon)
        return h, extra, None

    def logits(self, params, h):
        # the dense path's ln_f arithmetic (`F.layer_norm`, NOT the block
        # `_stacked_ln`) and lm_head einsum, so parity tracks the oracle
        from ..nn.functional import layer_norm_arrays

        hn = layer_norm_arrays(h, params["lnf_w"], params["lnf_b"],
                               epsilon=self.cfg.layer_norm_epsilon)
        return jnp.einsum("bsh,vh->bsv", hn, params["wte"])


def _zigzag_active(cfg):
    """True when the model-level zigzag context-parallel layout applies
    (mesh/config only; the caller validates seq divisibility)."""
    from ..parallel.mesh import axis_size

    return (cfg.context_parallel and cfg.cp_layout == "zigzag"
            and axis_size("sp") > 1 and axis_size("pp") <= 1
            and not cfg.attention_dropout_prob)


def _sample_next(logits, key, do_sample, temperature, top_k, top_p):
    """Next-token selection on [B, V] fp32 logits: greedy argmax, or
    temperature / top-k / nucleus (top-p) sampling."""
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    # ptpu-check[host-sync]: temperature/top_k are python-level sampling
    # config, closed over statically at trace time — never traced operands
    logits = logits / max(float(temperature), 1e-6)
    if top_k and top_k > 0:
        # ptpu-check[host-sync]: top_k is static python config (see above)
        kth = jnp.sort(logits, axis=-1)[:, -int(top_k)][:, None]
        logits = jnp.where(logits < kth, _NEG_INF, logits)
    if top_p is not None and top_p < 1.0:
        sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs <= top_p        # first token always kept
        thresh = jnp.min(jnp.where(keep, sorted_desc, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < thresh, _NEG_INF, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


_NEG_INF = -1e30


class GPTForCausalLM(Layer):
    """LM head ties the (vocab-parallel) embedding weight."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        self._gen_step = None       # (shapes key, jitted fn) decode cache

    def serving_form(self):
        """What `serving.LLMEngine` runs of this model."""
        return GPTServingForm(self)

    def __deepcopy__(self, memo):
        # the decode cache's jitted closure captures SELF — a deepcopy
        # carrying it would silently generate with the ORIGINAL model's
        # weights/state names (bites every copy-then-modify flow:
        # quantization swaps, lowbit packing, ensembling)
        import copy as _copy

        new = self.__class__.__new__(self.__class__)
        memo[id(self)] = new
        for k, v in self.__dict__.items():
            new.__dict__[k] = None if k == "_gen_step" \
                else _copy.deepcopy(v, memo)
        return new

    def forward(self, input_ids, position_ids=None, caches=None,
                time_step=None, segment_ids=None, cache_mask=None):
        if caches is not None:
            # segment_ids forwarded so GPTModel's loud guard fires instead
            # of silently decoding across document boundaries
            x, new_caches = self.gpt(input_ids, position_ids, caches=caches,
                                     time_step=time_step,
                                     segment_ids=segment_ids,
                                     cache_mask=cache_mask)
        else:
            x = self.gpt(input_ids, position_ids, segment_ids=segment_ids)
        w = self.gpt.embeddings.word_embeddings.weight
        logits = apply(
            lambda a, wt: jnp.einsum("bsh,vh->bsv", a, wt), x, w,
            name="lm_head",
        )
        # logits vocab dim carries the mp shard (parallel cross-entropy eats it)
        logits = constraint(
            logits, ["dp", "sp" if self.cfg.sequence_parallel else None, "mp"])
        if caches is not None:
            return logits, new_caches
        return logits

    def pretrain_loss(self, input_ids, labels, loss_mask=None,
                      segment_ids=None, position_ids=None):
        """Causal-LM training loss honoring cfg.pp_schedule.

        Under pp>1 with pp_schedule="1f1b" the blocks, final norm, LM head,
        and cross entropy all run inside the fused 1F1B pipeline
        (parallel/pipeline.py:pipeline_1f1b) so in-flight activations are
        bounded by pp depth — the reference train_batch path
        (meta_parallel/pipeline_parallel.py:230). Otherwise equivalent to
        GPTPretrainingCriterion()(self(input_ids), labels, loss_mask).
        """
        from ..parallel.mesh import axis_size
        from ..parallel.pipeline import pipeline_1f1b

        cfg = self.cfg
        if not (cfg.stacked_blocks and cfg.pp_schedule == "1f1b"
                and axis_size("pp") > 1):
            crit = GPTPretrainingCriterion(cfg)
            return crit(self(input_ids, position_ids,
                             segment_ids=segment_ids), labels, loss_mask)
        blocks = self.gpt.blocks
        names = blocks._names
        has_segs = segment_ids is not None
        block = blocks.block_closure(seg_as_arg=has_segs)
        n_micro = cfg.pp_num_microbatches or None
        eps = cfg.layer_norm_epsilon
        x = self.gpt.embeddings(input_ids, position_ids)
        wte = self.gpt.embeddings.word_embeddings.weight
        lnw, lnb = self.gpt.ln_f.weight, self.gpt.ln_f.bias
        has_mask = loss_mask is not None

        def loss_fn(tail, h, ymb):
            y_mb, mask_mb, scale_mb = ymb
            hn = _stacked_ln(h, tail["ln_w"], tail["ln_b"], eps)
            logits = jnp.einsum("bsh,vh->bsv", hn, tail["wte"])
            # hard-label CE as logsumexp - picked (no [.., V] log-prob
            # materialization — see nn/functional cross_entropy)
            lse = jax.scipy.special.logsumexp(
                logits.astype(jnp.float32), axis=-1)
            picked = jnp.take_along_axis(
                logits, y_mb[..., None].astype(jnp.int32), axis=-1
            )[..., 0].astype(jnp.float32)
            per_tok = lse - picked
            if has_mask:
                # scale_mb carries M/total_mask_count so the pipeline's
                # mean over micro-batches reproduces the criterion's GLOBAL
                # sum(loss*mask)/sum(mask) even when live-token counts
                # differ across micro-batches
                m = mask_mb.astype(jnp.float32)
                return jnp.sum(per_tok * m) * scale_mb[0]
            return jnp.mean(per_tok)

        mask_arg = loss_mask if has_mask else labels  # placeholder leaf
        seg_arg = segment_ids if has_segs else labels  # placeholder leaf

        def fn(a, y, mask, segs, wte_, lnw_, lnb_, *flat):
            params = dict(zip(names, flat))
            tail = {"wte": wte_, "ln_w": lnw_, "ln_b": lnb_}
            M = n_micro or axis_size("pp")
            if has_mask:
                total = jnp.clip(jnp.sum(mask.astype(jnp.float32)), 1.0)
            else:
                total = jnp.float32(1.0)
            # per-microbatch [B/M] replica of the global scale (pipeline
            # reshapes every y leaf along the batch dim)
            scale = jnp.full((a.shape[0],), M / total, jnp.float32)
            return pipeline_1f1b(block, loss_fn, params, tail, a,
                                 (y, mask, jax.lax.stop_gradient(scale)),
                                 n_microbatches=n_micro,
                                 aux=(jnp.asarray(segs, jnp.int32)
                                      if has_segs else None))

        tensors = [getattr(blocks, n) for n in names]
        return apply(fn, x, labels, mask_arg, seg_arg, wte, lnw, lnb,
                     *tensors, name="gpt_1f1b_loss")

    # -- autoregressive decoding -------------------------------------------
    def init_caches(self, batch_size, max_length, dtype=None):
        """Allocate static-shape KV caches (reference CacheKV:
        fused_multi_transformer_op.cu:90 — [2, B, H, S_max, D] per layer;
        here flat [B, S_max, H*D] rings — see cached_attention_arrays)."""
        cfg = self.cfg
        nh = cfg.num_attention_heads
        hd = cfg.hidden_size // nh
        if dtype is None:
            dtype = self.gpt.embeddings.word_embeddings.weight.dtype
        # flat [B, Smax, H*D] rings: the (H, D) split never reaches a
        # buffer, so XLA keeps a row-contiguous cache layout (no relayout
        # copies around the decode kernel, contiguous one-row writes).
        # Ring length rounds up to 128 so the decode kernels' tile-aligned
        # cache DMA gates pass at any requested length (only the valid
        # prefix is ever read; the extra rows are never touched).
        max_length = -(-max_length // 128) * 128
        shape = (batch_size, max_length, nh * hd)
        unroll_env = os.environ.get("PTPU_DECODE_UNROLL")
        unroll = (cfg.num_hidden_layers <= 32 if unroll_env is None
                  else unroll_env != "0")
        if cfg.stacked_blocks and not unroll:
            # very deep models: stacked [L, ...] caches → layer-scan decode
            full = (cfg.num_hidden_layers,) + shape
            return (Tensor(jnp.zeros(full, dtype)), Tensor(jnp.zeros(full, dtype)))
        return [
            (Tensor(jnp.zeros(shape, dtype)), Tensor(jnp.zeros(shape, dtype)))
            for _ in range(cfg.num_hidden_layers)
        ]

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 seed=None, pad_token_id=None):
        """KV-cache autoregressive decoding: prefill and the whole decode
        loop run as ONE compiled program per (shapes, sampling) key — the
        loop is an on-device while_loop over static cache shapes
        (lax.dynamic_update_slice ring writes), so a generate() call costs
        a single dispatch. Greedy by default; temperature / top-k / top-p
        sampling with do_sample=True.

        pad_token_id: enables RAGGED prompt batches — rows padded with
        this id (left- or right-padded; interior pads unsupported) are
        canonicalized to left-padding internally, pad positions are
        masked out of attention, and per-row positions restart after each
        row's real prompt (the reference generate's attention_mask
        semantics). The returned buffer is left-aligned: [pads | prompt |
        generated] per row.

        Returns [B, prompt + generated] int32 ids (generation stops early
        when every row has emitted eos_token_id).
        """
        from ..autograd import tape as _tape
        from ..core import random as _rng

        cfg = self.cfg
        ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        if max_new_tokens <= 0:
            # nothing to generate: the decode trace cannot even be built
            # (its token buffer would be [B, 0])
            return Tensor(ids)
        B, P = ids.shape
        total = P + max_new_tokens
        if total > cfg.max_position_embeddings:
            raise ValueError(
                f"prompt ({P}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"max_position_embeddings ({cfg.max_position_embeddings})")

        model = self
        was_training = self.training
        self.eval()

        padded = pad_token_id is not None

        def run_fwd(params, bufs, chunk, caches, t, static_prefill=False,
                    position_ids=None, cache_mask=None):
            # static_prefill (t == 0 STATICALLY) selects the flash-prefill
            # branch: causal flash over the chunk + cache write, instead
            # of the O(S * S_max) masked path a traced t forces
            backup = model.state_arrays()
            try:
                model.load_state_arrays(params, bufs)
                with _tape.no_grad():
                    logits, new_caches = model(
                        Tensor(chunk),
                        position_ids=(None if position_ids is None
                                      else Tensor(position_ids)),
                        caches=jax.tree.map(Tensor, caches),
                        time_step=None if static_prefill else Tensor(t),
                        cache_mask=(None if cache_mask is None
                                    else Tensor(cache_mask)),
                    )
                last = logits._data[:, -1].astype(jnp.float32)
                return last, jax.tree.map(lambda c: c._data, new_caches,
                                          is_leaf=lambda c: isinstance(c, Tensor))
            finally:
                model.load_state_arrays(*backup)

        def generate_all(params, bufs, ids_in, caches, key):
            """Prefill + the WHOLE decode loop as ONE program: a
            host-driven token loop pays a dispatch round-trip per step
            and even a separate prefill dispatch doubles the fixed per-call cost, so both
            live in one jitted call with the loop as an on-device
            while_loop. Early EOS exit survives as the loop condition;
            the emitted count comes back so the host can trim to the
            host-loop-identical length."""
            shift = None
            cache_mask = None
            pos_prefill = None
            if padded:
                # canonicalize ragged rows to LEFT padding: roll each row
                # so its real tokens end at column P-1 — decode then
                # writes uniform cache rows while positions/attention
                # stay per-row exact. TWO distinct quantities: the roll
                # amount comes from the LAST valid index (0 for already
                # left-padded rows), while masks/positions need the PAD
                # COUNT (nonzero for left-padded rows too — deriving both
                # from the roll silently unmasked left-pads).
                valid = ids_in != pad_token_id
                last1 = jnp.max(jnp.where(
                    valid, jnp.arange(1, P + 1)[None, :], 0), axis=1)
                roll = (P - last1).astype(jnp.int32)             # [B]
                shift = (P - jnp.sum(valid, axis=1)).astype(jnp.int32)
                cols = jnp.arange(P, dtype=jnp.int32)[None, :]
                idx = (cols - roll[:, None]) % P
                ids_in = jnp.take_along_axis(ids_in, idx, axis=1)
                ids_in = jnp.where(cols >= shift[:, None], ids_in,
                                   pad_token_id)
                pos_prefill = jnp.maximum(cols - shift[:, None], 0)
                s_max = jax.tree_util.tree_leaves(caches)[0].shape[-2]
                j = jnp.arange(s_max, dtype=jnp.int32)[None, :]
                invalid = (j < shift[:, None]) & (j < P)
                cache_mask = jnp.where(invalid, jnp.float32(_NEG_INF),
                                       0.0)[:, None, None, :]
            logits, caches = run_fwd(params, bufs, ids_in, caches,
                                     jnp.asarray(0, jnp.int32),
                                     static_prefill=True,
                                     position_ids=pos_prefill,
                                     cache_mask=cache_mask)
            finished0 = jnp.zeros((B,), bool)
            toks0 = jnp.zeros((B, max_new_tokens), jnp.int32)

            def cond_fn(st):
                i, _logits, _caches, _key, finished, _toks = st
                live = i < max_new_tokens
                if eos_token_id is not None:
                    live = live & ~jnp.all(finished)
                return live

            def one_step(st):
                i, logits, caches, key, finished, toks = st
                if do_sample:
                    key, sub = jax.random.split(key)
                else:
                    sub = None
                tok = _sample_next(logits, sub, do_sample, temperature,
                                   top_k, top_p)
                if eos_token_id is not None:
                    tok = jnp.where(finished, eos_token_id, tok)
                    finished = finished | (tok == eos_token_id)
                toks = jax.lax.dynamic_update_slice(
                    toks, tok[:, None].astype(jnp.int32), (0, i))
                # skip the forward after the final token (its logits are
                # never sampled) — matches the host loop's `i+1 < max_new`
                # guard and its break-before-forward on all-rows-EOS
                more = i + 1 < max_new_tokens
                if eos_token_id is not None:
                    more = more & ~jnp.all(finished)
                def fwd(c):
                    pos = None
                    if padded:
                        # per-row position: row length + generated count
                        pos = (P + i - shift)[:, None]
                    return run_fwd(params, bufs, tok[:, None], c, P + i,
                                   position_ids=pos,
                                   cache_mask=cache_mask)

                logits, caches = jax.lax.cond(
                    more, fwd, lambda c: (logits, c), caches)
                return (i + 1, logits, caches, key, finished, toks)

            unroll = max(1, int(os.environ.get(
                "PTPU_DECODE_STEP_UNROLL", "1")))

            if unroll == 1:
                body_fn = one_step
            else:
                # U token steps inside one while trip: trip boundaries are
                # scheduling barriers, so unrolling lets XLA overlap step
                # i+1's weight streams with step i's tail. Overshoot
                # substeps (final trip, or after all rows hit EOS) are
                # identity via the cond guard; every trip the outer cond
                # admits advances i by >= 1, so termination is unchanged.
                def body_fn(st):
                    for _ in range(unroll):
                        st = jax.lax.cond(cond_fn(st), one_step,
                                          lambda s: s, st)
                    return st

            i0 = jnp.asarray(0, jnp.int32)
            i, _, caches, _, _, toks = jax.lax.while_loop(
                cond_fn, body_fn,
                (i0, logits, caches, key, finished0, toks0))
            # caches ride out as outputs ONLY so donate_argnums=(3,) has
            # something to alias: unmatched donations are "not usable"
            # (jax warns) and XLA then copies every cache at entry instead
            # of mutating the donated buffers in place. ids_in rides out
            # so padded batches return the canonicalized (left-aligned)
            # prompt the generated tokens actually continue.
            return i, toks, ids_in, caches

        # executable cache: sampling params AND the step-unroll factor are
        # baked into the decode trace
        gen_key = (B, P, total, cfg.stacked_blocks, do_sample, temperature,
                   top_k, top_p, eos_token_id, pad_token_id,
                   os.environ.get("PTPU_DECODE_STEP_UNROLL", "1"))
        if self._gen_step is None or self._gen_step[0] != gen_key:
            self._gen_step = (gen_key,
                              jax.jit(generate_all, donate_argnums=(3,)))
        gen_step = self._gen_step[1]

        params, bufs = self.state_arrays()
        caches = self.init_caches(B, total)
        cache_arrs = jax.tree.map(
            lambda c: c._data, caches, is_leaf=lambda c: isinstance(c, Tensor))

        key = ((jax.random.PRNGKey(seed) if seed is not None
                else _rng.next_key()) if do_sample
               else jax.random.PRNGKey(0))

        n, toks, ids_out, _ = gen_step(params, bufs, ids, cache_arrs, key)
        n = int(n)

        if was_training:
            self.train()
        return Tensor(jnp.concatenate([ids_out, toks[:, :n]], axis=1))


class GPTPretrainingCriterion(Layer):
    """Vocab-parallel cross entropy (reference:
    c_softmax_with_cross_entropy_op.cu)."""

    def __init__(self, cfg: Optional[GPTConfig] = None):
        super().__init__()
        self.ce = ParallelCrossEntropy()

    def forward(self, logits, labels, loss_mask=None):
        loss = self.ce(logits, labels)
        if loss_mask is not None:
            loss = loss * loss_mask
            return loss.sum() / loss_mask.sum().clip(min=1.0)
        return loss.mean()
