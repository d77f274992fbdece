"""incubate.nn fused layers (reference: python/paddle/incubate/nn/ —
FusedMultiHeadAttention, FusedFeedForward backed by fused_attention_op.cu /
fused_feedforward_op.cu). TPU-native: flash attention (Pallas) + XLA-fused
FFN."""
from __future__ import annotations

import jax.numpy as jnp

from ...nn.layer import Layer
from ...nn.common import Linear, Dropout
from ...nn.norm import LayerNorm
from ...nn.initializer import Constant
from ...nn import container as nn_container
from ...nn import functional as F

__all__ = ["FusedMultiHeadAttention", "FusedFeedForward",
           "FusedMultiTransformer", "FusedMultiTransformerInt8",
           "FusedEcMoe", "fused_ec_moe", "functional"]


class FusedMultiHeadAttention(Layer):
    def __init__(self, embed_dim, num_heads, dropout_rate=0.5,
                 attn_dropout_rate=0.5, kdim=None, vdim=None,
                 normalize_before=False, need_weights=False, qkv_weight_attr=None,
                 qkv_bias_attr=None, linear_weight_attr=None, linear_bias_attr=None,
                 pre_ln_scale_attr=None, pre_ln_bias_attr=None, ln_scale_attr=None,
                 ln_bias_attr=None, epsilon=1e-5, nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.normalize_before = normalize_before
        self.qkv = Linear(embed_dim, 3 * embed_dim, qkv_weight_attr, qkv_bias_attr)
        self.out_proj = Linear(embed_dim, embed_dim, linear_weight_attr, linear_bias_attr)
        self.pre_ln = LayerNorm(embed_dim, epsilon)
        self.post_ln = LayerNorm(embed_dim, epsilon)
        self.attn_dropout_rate = attn_dropout_rate
        self.dropout = Dropout(dropout_rate)

    def forward(self, query, key=None, value=None, attn_mask=None, cache=None):
        from ...ops.manipulation import reshape, split

        residual = query
        x = self.pre_ln(query) if self.normalize_before else query
        b, s, _ = x.shape
        qkv = self.qkv(x)
        q, k, v = split(qkv, 3, axis=-1)
        q = reshape(q, [b, s, self.num_heads, self.head_dim])
        k = reshape(k, [b, s, self.num_heads, self.head_dim])
        v = reshape(v, [b, s, self.num_heads, self.head_dim])
        out = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.attn_dropout_rate,
            training=self.training,
        )
        out = reshape(out, [b, s, self.embed_dim])
        out = self.dropout(self.out_proj(out))
        out = residual + out
        if not self.normalize_before:
            out = self.post_ln(out)
        return out


class FusedFeedForward(Layer):
    def __init__(self, d_model, dim_feedforward, dropout_rate=0.1, epsilon=1e-5,
                 activation="relu", act_dropout_rate=None, normalize_before=False,
                 linear1_weight_attr=None, linear1_bias_attr=None,
                 linear2_weight_attr=None, linear2_bias_attr=None,
                 ln1_scale_attr=None, ln1_bias_attr=None, ln2_scale_attr=None,
                 ln2_bias_attr=None, nranks=1, ring_id=-1, name=None):
        super().__init__()
        self.normalize_before = normalize_before
        self.linear1 = Linear(d_model, dim_feedforward, linear1_weight_attr, linear1_bias_attr)
        self.linear2 = Linear(dim_feedforward, d_model, linear2_weight_attr, linear2_bias_attr)
        self.ln = LayerNorm(d_model, epsilon)
        self.dropout1 = Dropout(act_dropout_rate if act_dropout_rate is not None else dropout_rate)
        self.dropout2 = Dropout(dropout_rate)
        self.activation = activation

    def forward(self, src, cache=None):
        residual = src
        x = self.ln(src) if self.normalize_before else src
        x = self.linear2(self.dropout1(getattr(F, self.activation)(self.linear1(x))))
        x = residual + self.dropout2(x)
        if not self.normalize_before:
            x = self.ln(x)
        return x


class FusedMultiTransformer(Layer):
    """Stacked fused transformer decoder layers (reference:
    python/paddle/incubate/nn/layer/fused_transformer.py
    FusedMultiTransformer over fused_multi_transformer_op.cu): pre-LN
    attention + FFN per layer, all heavy math in flash attention (Pallas)
    and XLA-fused matmuls."""

    def __init__(self, embed_dim, num_heads, dim_feedforward, dropout_rate=0.0,
                 activation="gelu", normalize_before=True, num_layers=1,
                 epsilon=1e-5, nranks=1, ring_id=-1, name=None):
        super().__init__()
        if not normalize_before:
            raise ValueError("FusedMultiTransformer is pre-LN (reference contract)")
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.head_dim = embed_dim // num_heads
        self.activation = activation
        self.epsilon = epsilon
        self.dropout_rate = dropout_rate
        layers = []
        for _ in range(num_layers):
            layers.append(nn_container.LayerDict({
                "ln1": LayerNorm(embed_dim, epsilon=epsilon),
                "qkv": Linear(embed_dim, 3 * embed_dim),
                "out": Linear(embed_dim, embed_dim),
                "ln2": LayerNorm(embed_dim, epsilon=epsilon),
                "ffn1": Linear(embed_dim, dim_feedforward),
                "ffn2": Linear(dim_feedforward, embed_dim),
            }))
        self.layers = nn_container.LayerList(layers)
        self.dropout = Dropout(dropout_rate)

    @staticmethod
    def _cached_attn(q, k, v, cache, t, mask=None):
        """Array-level CacheKV attention. cache: [2, B, H, S_max, D]
        (reference layout, fused_multi_transformer_op.cu:90); q/k/v:
        [B, S, H, D]; t = real current length of the cache (the chunk is
        written starting at t); mask broadcastable to [B, H, S, S_max].
        Returns (out, new_cache)."""
        from ...ops.pallas_ops import cached_attention_arrays

        kc = jnp.moveaxis(cache[0], 1, 2)        # -> [B, S_max, H, D]
        vc = jnp.moveaxis(cache[1], 1, 2)
        o, kc, vc = cached_attention_arrays(q, k, v, kc, vc, t, mask=mask)
        new_cache = jnp.stack(
            [jnp.moveaxis(kc, 2, 1), jnp.moveaxis(vc, 2, 1)])
        return o, new_cache

    def gen_cache(self, batch_size, max_length, dtype="float32"):
        """Allocate per-layer CacheKV tensors:
        [2, bsz, num_head, max_seq_len, head_dim] (the fused op's CUDA
        layout)."""
        from ...core.tensor import Tensor

        shape = (2, batch_size, self.num_heads, max_length, self.head_dim)
        return [Tensor(jnp.zeros(shape, dtype)) for _ in range(self.num_layers)]

    def _proj(self, li, name, x):
        """One of the four heavy matmuls of layer li ('qkv', 'out',
        'ffn1', 'ffn2') — the quantized subclass reroutes exactly this."""
        return self.layers[li][name](x)

    def forward(self, src, attn_mask=None, caches=None, time_step=None):
        from ...core.dispatch import apply
        from ...ops.pallas_ops import flash_attention

        if caches is not None and len(caches) != self.num_layers:
            raise ValueError(
                f"caches must have one [2,B,H,S,D] tensor per layer "
                f"({self.num_layers}), got {len(caches)}")

        x = src
        B = None
        new_caches = []
        act = F.gelu if self.activation == "gelu" else F.relu
        for li, blk in enumerate(self.layers):
            h = blk["ln1"](x)
            qkv = self._proj(li, "qkv", h)
            if B is None:
                B, S, _ = qkv.shape
            q, k, v = qkv.reshape([B, S, 3, self.num_heads, self.head_dim]).unbind(axis=2)
            if caches is not None:
                t = 0 if time_step is None else time_step
                if attn_mask is not None:
                    # mask applies over cache positions: [B, H|1, S, S_max]
                    attn, new_cache = apply(
                        self._cached_attn, q, k, v, caches[li], t, attn_mask,
                        name="fused_cached_attention")
                else:
                    attn, new_cache = apply(
                        self._cached_attn, q, k, v, caches[li], t,
                        name="fused_cached_attention")
                # reference CacheKV is written in place by the fused op;
                # mirror that for eager callers while also returning the
                # updated caches for functional (traced) use
                caches[li]._data = new_cache._data
                new_caches.append(new_cache)
            else:
                attn = flash_attention(q, k, v, attn_mask=attn_mask,
                                       is_causal=attn_mask is None)
            x = x + self.dropout(self._proj(li, "out", attn.reshape([B, S, -1])))
            h = blk["ln2"](x)
            x = x + self.dropout(
                self._proj(li, "ffn2", act(self._proj(li, "ffn1", h))))
        if caches is not None:
            return x, new_caches
        return x


class FusedMultiTransformerInt8(FusedMultiTransformer):
    """Int8 stacked transformer (reference:
    fused_multi_transformer_int8_op.cu + attn_gemm_int8.h — per-layer
    int8 GEMMs with dequant rescale; inference-only, like the reference op).

    TPU-native quantization recipe:
    - weights are stored int8 with per-output-channel fp32 scales
      (halves/quarters weight HBM, the dominant decode-time traffic),
    - act_quant="dynamic" (default) also quantizes activations per tensor
      at runtime and runs int8 x int8 -> int32 dot_general — the MXU has a
      native int8 path — then dequantizes by act_scale * w_scale,
    - act_quant="none" is weight-only: dequantize weights into the
      activation dtype on the fly (robust to outlier activations).

    Build one with `FusedMultiTransformerInt8.from_float(fmt)` to quantize
    an existing FusedMultiTransformer, or construct directly and call
    load-state on the float twin before `quantize_()`.
    """

    def __init__(self, embed_dim, num_heads, dim_feedforward,
                 dropout_rate=0.0, activation="gelu", normalize_before=True,
                 num_layers=1, epsilon=1e-5, nranks=1, ring_id=-1,
                 act_quant="dynamic", name=None):
        super().__init__(embed_dim, num_heads, dim_feedforward,
                         dropout_rate, activation, normalize_before,
                         num_layers, epsilon, nranks, ring_id, name)
        if act_quant not in ("dynamic", "none"):
            raise ValueError("act_quant must be 'dynamic' or 'none'")
        self.act_quant = act_quant
        self._qweights = None   # [{name: (int8 w, f32 scale)}] per layer

    _QNAMES = ("qkv", "out", "ffn1", "ffn2")

    def quantize_(self, free_float=True):
        """Quantize the current float weights (per-out-channel symmetric
        int8, reference round-to-nearest with 127 bound). free_float=True
        (default) releases the float weight buffers so the advertised
        weight-HBM saving is real; state_dict() then materializes
        dequantized weights on demand."""
        qw = []
        for blk in self.layers:
            entry = {}
            for nm in self._QNAMES:
                w = blk[nm].weight._data.astype(jnp.float32)   # [in, out]
                scale = jnp.max(jnp.abs(w), axis=0) / 127.0
                scale = jnp.maximum(scale, 1e-8)
                wi8 = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
                entry[nm] = (wi8, scale, tuple(w.shape), blk[nm].weight.dtype)
                if free_float:
                    blk[nm].weight._data = jnp.zeros((), blk[nm].weight.dtype)
            qw.append(entry)
        self._qweights = qw
        return self

    def state_dict(self, *a, **k):
        """Materialize dequantized weights for the freed float params so
        checkpoints of a quantized module stay loadable by the float
        twin (values carry the quantization error, as expected). The
        entries are FRESH tensors — the module's own freed buffers stay
        freed."""
        from ...core.tensor import Tensor as _T

        out = super().state_dict(*a, **k)
        if self._qweights is None:
            return out
        freed = {}
        for blk, entry in zip(self.layers, self._qweights):
            for nm, (wi8, scale, shape, dt) in entry.items():
                freed[id(blk[nm].weight)] = (wi8, scale, dt)
        for key, t in list(out.items()):
            hit = freed.get(id(t))
            if hit is not None:
                wi8, scale, dt = hit
                out[key] = _T((wi8.astype(jnp.float32) * scale).astype(dt))
        return out

    @classmethod
    def from_float(cls, fmt: "FusedMultiTransformer", act_quant="dynamic"):
        embed = fmt.num_heads * fmt.head_dim
        ffn = fmt.layers[0]["ffn1"].weight.shape[1]
        q = cls(embed, fmt.num_heads, ffn, dropout_rate=fmt.dropout_rate,
                activation=fmt.activation, num_layers=fmt.num_layers,
                epsilon=fmt.epsilon, act_quant=act_quant)
        q.set_state_dict(fmt.state_dict())
        return q.quantize_()

    def _proj(self, li, nm, x):
        """Reroute the parent's four heavy matmuls through int8."""
        if self._qweights is None:
            raise RuntimeError(
                "FusedMultiTransformerInt8 weights are not quantized yet — "
                "call quantize_() (or build via from_float)")
        return self._q_linear(x, li, nm)

    def _q_linear(self, x, li, nm):
        """x @ W through the int8 path (+ float bias)."""
        from ...core.dispatch import apply

        wi8, scale = self._qweights[li][nm][:2]
        bias = self.layers[li][nm].bias
        dynamic = self.act_quant == "dynamic"

        def fn(a, w, s, *maybe_b):
            import jax

            if dynamic:
                amax = jnp.maximum(jnp.max(jnp.abs(a)), 1e-8)
                s_a = (amax / 127.0).astype(jnp.float32)
                ai8 = jnp.clip(jnp.round(a / s_a.astype(a.dtype)),
                               -127, 127).astype(jnp.int8)
                acc = jax.lax.dot_general(
                    ai8, w, (((a.ndim - 1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                out = acc.astype(jnp.float32) * (s_a * s)
            else:
                out = a @ (w.astype(a.dtype) * s.astype(a.dtype))
            out = out.astype(a.dtype)
            if maybe_b:
                out = out + maybe_b[0]
            return out

        args = [x, wi8, scale]
        if bias is not None:
            args.append(bias)
        return apply(fn, *args, name=f"int8_{nm}")



def fused_ec_moe(x, gate, bmm0_weight, bmm0_bias, bmm1_weight, bmm1_bias,
                 act_type="gelu"):
    """Expert-choice MoE (reference: incubate/nn/functional/fused_ec_moe
    over fused_moe_kernel.cu): each expert selects its top seq_len/16
    tokens by gate logit, applies its FFN as one batched einsum over the
    expert dim (MXU-friendly — no host-side grouping), and the outputs
    scatter back weighted by the softmax gate probability, residual-added.

    x [B,S,D]; gate [B,S,E]; bmm0_weight [E,D,F]; bmm0_bias [E,1,F];
    bmm1_weight [E,F,D]; bmm1_bias [E,1,D].
    """
    from ...core.dispatch import apply
    import jax

    if act_type not in ("gelu", "relu"):
        raise ValueError("act_type must be 'gelu' or 'relu'")

    def fn(xa, g, w0, b0, w1, b1):
        B, S, D = xa.shape
        E = g.shape[-1]
        cap = max(S // 16, 1)           # reference capacity rule
        probs = jax.nn.softmax(g, axis=-1)            # [B,S,E]
        logits_e = jnp.swapaxes(g, 1, 2)              # [B,E,S]
        _, idx = jax.lax.top_k(logits_e, cap)         # [B,E,cap]
        sel = jnp.take_along_axis(
            xa[:, None], idx[..., None], axis=2)      # [B,E,cap,D]
        h = jnp.einsum("becd,edf->becf", sel, w0,
                       preferred_element_type=jnp.float32).astype(xa.dtype)
        h = h + b0                # [E,1,F] broadcasts over [B,E,cap,F]
        h = jax.nn.gelu(h, approximate=True) if act_type == "gelu" \
            else jax.nn.relu(h)
        o = jnp.einsum("becf,efd->becd", h, w1,
                       preferred_element_type=jnp.float32).astype(xa.dtype)
        o = o + b1                # [E,1,D] broadcasts over [B,E,cap,D]
        p = jnp.take_along_axis(jnp.swapaxes(probs, 1, 2), idx, axis=2)
        o = o * p[..., None]                          # [B,E,cap,D]
        out = jnp.zeros_like(xa)
        b_ix = jnp.broadcast_to(jnp.arange(B)[:, None, None], idx.shape)
        out = out.at[b_ix.reshape(-1), idx.reshape(-1)].add(
            o.reshape(-1, D))
        return xa + out

    return apply(fn, x, gate, bmm0_weight, bmm0_bias, bmm1_weight,
                 bmm1_bias, name="fused_ec_moe")


class FusedEcMoe(Layer):
    """Layer form (reference: incubate/nn/layer/fused_ec_moe.py
    FusedEcMoe). forward(x, gate) -> [B, S, D]."""

    def __init__(self, hidden_size, inter_size, num_expert, act_type="gelu",
                 weight_attr=None, bias_attr=None):
        super().__init__()
        from ...nn.initializer import XavierNormal, Constant

        if act_type not in ("gelu", "relu"):   # fail at construction
            raise ValueError("act_type must be 'gelu' or 'relu'")
        self.act_type = act_type
        self.bmm_weight0 = self.create_parameter(
            shape=[num_expert, hidden_size, inter_size], attr=weight_attr,
            default_initializer=XavierNormal())
        self.bmm_bias0 = self.create_parameter(
            shape=[num_expert, 1, inter_size], attr=bias_attr,
            default_initializer=Constant(0.0))
        self.bmm_weight1 = self.create_parameter(
            shape=[num_expert, inter_size, hidden_size], attr=weight_attr,
            default_initializer=XavierNormal())
        self.bmm_bias1 = self.create_parameter(
            shape=[num_expert, 1, hidden_size], attr=bias_attr,
            default_initializer=Constant(0.0))

    def forward(self, x, gate):
        return fused_ec_moe(x, gate, self.bmm_weight0, self.bmm_bias0,
                            self.bmm_weight1, self.bmm_bias1, self.act_type)


from . import functional  # noqa: E402  (needs fused_ec_moe above)


class FusedLinear(Layer):
    """Linear whose matmul+bias ride one fused XLA kernel (reference
    incubate/nn/layer/fc.py FusedLinear over fused_gemm_epilogue)."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 bias_attr=None, transpose_weight=False, name=None):
        super().__init__()
        self._transpose = transpose_weight
        shape = ([out_features, in_features] if transpose_weight
                 else [in_features, out_features])
        self.weight = self.create_parameter(shape, attr=weight_attr)
        self.bias = self.create_parameter([out_features], attr=bias_attr,
                                          is_bias=True)

    def forward(self, x):
        from .functional import fused_linear

        return fused_linear(x, self.weight, self.bias,
                            transpose_weight=self._transpose)


class FusedBiasDropoutResidualLayerNorm(Layer):
    """out = LayerNorm(residual + dropout(x + bias)) in one fused region
    (reference incubate/nn/layer/fused_transformer.py
    FusedBiasDropoutResidualLayerNorm)."""

    def __init__(self, embed_dim, dropout_rate=0.5, weight_attr=None,
                 bias_attr=None, epsilon=1e-5, name=None):
        super().__init__()
        self.embed_dim = embed_dim
        self.dropout_rate = dropout_rate
        self.epsilon = epsilon
        self.linear_bias = self.create_parameter([embed_dim], attr=bias_attr,
                                                 is_bias=True)
        self.ln_scale = self.create_parameter(
            [embed_dim], attr=weight_attr,
            default_initializer=Constant(1.0))
        self.ln_bias = self.create_parameter([embed_dim], is_bias=True)

    def forward(self, x, residual):
        from .functional import fused_bias_dropout_residual_layer_norm

        return fused_bias_dropout_residual_layer_norm(
            x, residual, bias=self.linear_bias, ln_scale=self.ln_scale,
            ln_bias=self.ln_bias, dropout_rate=self.dropout_rate,
            ln_epsilon=self.epsilon, training=self.training)


class FusedTransformerEncoderLayer(Layer):
    """Encoder layer over the fused attention + FFN ops (reference
    incubate/nn/layer/fused_transformer.py FusedTransformerEncoderLayer)."""

    def __init__(self, d_model, nhead, dim_feedforward, dropout_rate=0.1,
                 activation="relu", attn_dropout_rate=None,
                 act_dropout_rate=None, normalize_before=False,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        self.normalize_before = normalize_before
        # None defaults to dropout_rate (reference fused_transformer.py)
        attn_dropout_rate = (dropout_rate if attn_dropout_rate is None
                             else attn_dropout_rate)
        act_dropout_rate = (dropout_rate if act_dropout_rate is None
                            else act_dropout_rate)
        self.attn = FusedMultiHeadAttention(
            d_model, nhead, dropout_rate=dropout_rate,
            attn_dropout_rate=attn_dropout_rate,
            normalize_before=normalize_before)
        self.ffn = FusedFeedForward(
            d_model, dim_feedforward, dropout_rate=dropout_rate,
            activation=activation, act_dropout_rate=act_dropout_rate,
            normalize_before=normalize_before)

    def forward(self, src, src_mask=None, cache=None):
        if cache is not None:
            raise NotImplementedError(
                "FusedTransformerEncoderLayer incremental cache is not "
                "wired; use FusedMultiTransformer's CacheKV decode path "
                "(gen_cache + time_step) for autoregressive decoding")
        return self.ffn(self.attn(src, attn_mask=src_mask))


__all__ += ["FusedLinear", "FusedBiasDropoutResidualLayerNorm",
            "FusedTransformerEncoderLayer"]
