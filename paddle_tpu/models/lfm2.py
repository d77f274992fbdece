"""lfm2_moe — the LiquidAI LFM2 mixture-of-experts decoder family
(`model_type: lfm2_moe`; LFM2-24B-A2B is 40 layers of hidden 2048, 64
experts top-4, about 2B of 24B parameters active).

The block (`benchmark/lib/reference_lfm2.py` is the independent float32
statement of the same equations): plain token embedding; pre-norm only,
`h += op(rmsnorm(h))` then `h += ff(rmsnorm(h))`; `op` by `layer_types[l]`
either a GATED SHORT CONVOLUTION - `[B, C, x] = split(a @ W_in)`, a
depthwise causal convolution of `conv_L_cache` taps over `u = B * x`,
`(C * conv) @ W_out`: no K/V at all, its memory of a sequence is the last
`conv_L_cache - 1` columns of `u` - or grouped-query attention with
RMSNorm over every q and k head and rotary positions; `ff` a dense SwiGLU
in the `num_dense_layers` leading layers, then a sigmoid-routed top-k
mixture of SwiGLU experts with no shared expert; final RMSNorm
(`embedding_norm`) and a head tied to the embedding.

`num_experts` counts the experts HELD here (ids `first_expert ..`),
`router_experts` the router's published width, as in models/afmoe.py;
LFM2-24B-A2B's 64 experts of a layer fit one chip, so its benchmark
configuration holds 64 of 64.

Weights are kept a layer at a time, each kind of layer indexed over its
own layers (`params()["conv_w"][i]` is the i-th CONVOLUTION layer's), and
`forward_arrays` takes them as arguments (PERF.md, PR 28).
`serving_form()` is what the engine runs: an attention layer names a
`LayerSpec`, a convolution layer a `StateSpec` of shape `[conv_L_cache - 1,
hidden]` in group "conv" (`models.serving_form`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import apply
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer
from ..parallel.moe import held_experts_arrays
from .afmoe import rms_norm, rope, swiglu
from .serving_form import LayerSpec, ServingForm, StateSpec

__all__ = ["Lfm2MoeConfig", "Lfm2MoeForCausalLM", "lfm2_test_config"]

CONV, FULL = "conv", "full_attention"


@dataclasses.dataclass
class Lfm2MoeConfig:
    """The published `config.json` keys under their own names, the sizes
    the config leaves to the family's code (`head_dim`,
    `tie_word_embeddings`, `initializer_range`), and the two that say
    which experts live here."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: Optional[int] = None      # default hidden / heads
    num_experts: int = 64               # experts held here
    num_experts_per_tok: int = 4
    conv_L_cache: int = 3
    conv_bias: bool = False
    layer_types: Optional[list] = None  # default: attention at l % 4 == 2
    max_position_embeddings: int = 128000
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    rope_parameters: Optional[dict] = None
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    model_type: str = "lfm2_moe"
    # which experts this chip holds
    router_experts: Optional[int] = None   # router width; default num_experts
    first_expert: int = 0

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [FULL if i % 4 == 2 else CONV
                                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.rope_parameters is None:
            self.rope_parameters = {"rope_theta": 1000000.0,
                                    "rope_type": "default"}
        if self.router_experts is None:
            self.router_experts = self.num_experts
        stated = {"conv_bias": False, "norm_topk_prob": True,
                  "use_expert_bias": True, "tie_word_embeddings": True}
        for key, want in stated.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"lfm2_moe is implemented for {key}={want!r}; got "
                    f"{getattr(self, key)!r}")
        if self.rope_parameters.get("rope_type", "default") != "default":
            raise ValueError("lfm2_moe is implemented for the default "
                             "rotary positions")
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in (CONV, FULL) for t in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {CONV!r} or {FULL!r}")
        if FULL not in self.layer_types:
            raise ValueError("lfm2_moe needs at least one attention layer")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache must be at least 2")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.first_expert + self.num_experts > self.router_experts:
            raise ValueError(
                f"experts {self.first_expert}.."
                f"{self.first_expert + self.num_experts - 1} held, but the "
                f"router is {self.router_experts} wide")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers out of range")

    @property
    def rope_theta(self) -> float:
        return float(self.rope_parameters["rope_theta"])


def lfm2_test_config(**kw):
    """The CPU tests' size: 1 dense + 6 expert layers, two periods of
    `attention, conv, conv` after the leading convolution, 8 experts
    top-2, 4 query heads over 2 of 16."""
    base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=7,
                num_attention_heads=4, num_key_value_heads=2,
                intermediate_size=128, moe_intermediate_size=32,
                num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
                layer_types=[CONV, FULL, CONV, CONV, FULL, CONV, CONV],
                max_position_embeddings=256)
    base.update(kw)
    return Lfm2MoeConfig(**base)


_CONV = ("conv_in_w", "conv_w", "conv_out_w")
_ATTN = ("q_w", "k_w", "v_w", "o_w", "q_norm", "k_norm")
_DENSE = ("dense_gate_w", "dense_up_w", "dense_down_w")
_MOE = ("router_w", "expert_bias", "exp_gate_w", "exp_up_w", "exp_down_w")


def short_conv(u, prev, w):
    """Depthwise causal convolution of K taps over `u` [B, S, H] behind
    the K - 1 columns `prev` [B, K-1, H] that came before it (zeros at a
    sequence's start): `c[t] = sum_k w[:, k] * u[t - (K-1) + k]`, summed
    in float32.  -> (c [B, S, H], the last K - 1 columns of `prev ++ u`:
    the state after this chunk)."""
    s = u.shape[1]
    full = jnp.concatenate([prev.astype(u.dtype), u], axis=1)
    wf = w.astype(jnp.float32)
    c = sum(wf[:, k] * full[:, k:k + s].astype(jnp.float32)
            for k in range(w.shape[1]))
    return c.astype(u.dtype), full[:, s:]


class Lfm2ServingForm(ServingForm):
    """What `LLMEngine` runs of an `Lfm2MoeForCausalLM`."""

    # `held_experts_arrays`' four counts, under afmoe's names
    stat_counters = (("serving/moe_pairs", {"where": "held"}),
                     ("serving/moe_pairs", {"where": "absent"}),
                     ("serving/moe_experts_touched", {}),
                     ("serving/moe_tokens", {}))
    # a verify step would have to roll the state back, a prefix hit would
    # need the state at the prefix's end, and the window-less group's int8
    # pools would need the grouped kernel: none is built
    unsupported = ("kv_cache_dtype", "speculative_tokens",
                   "enable_prefix_caching")

    def __init__(self, model):
        cfg = model.cfg
        self.model, self.cfg = model, cfg
        self.vocab_size = cfg.vocab_size
        self.max_position_embeddings = cfg.max_position_embeddings
        self.layer_specs = [
            LayerSpec(cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim, None, "full") if t == FULL
            else StateSpec((cfg.conv_L_cache - 1, cfg.hidden_size), "conv")
            for t in cfg.layer_types]
        # a layer's index among the layers of its own kind
        self._nth = [cfg.layer_types[:l].count(t)
                     for l, t in enumerate(cfg.layer_types)]

    @property
    def dtype(self):
        return self.model.embed.dtype

    def params(self):
        return self.model.param_arrays()

    def embed(self, params, ids, pos):
        return jnp.take(params["embed"], ids, axis=0)

    def layer(self, l, params, h, pos, cache_fn, valid=None):
        """`cache_fn` is the engine's `attn_fn` in an attention layer and
        its `state_fn` in a convolution layer (`ServingForm.layer`)."""
        cfg = self.cfg
        eps = cfg.norm_eps
        b, s, hidden = h.shape
        i = self._nth[l]
        a = rms_norm(h, params["operator_norm"][l], eps)
        if cfg.layer_types[l] == CONV:
            p = {n: params[n][i] for n in _CONV}
            with jax.named_scope("lfm2/conv"):
                gate_in, gate_out, x = jnp.split(a @ p["conv_in_w"], 3,
                                                 axis=-1)
                u = gate_in * x
                c, extra = cache_fn(
                    lambda prev: short_conv(u, prev, p["conv_w"]))
                y = (gate_out * c) @ p["conv_out_w"]
        else:
            p = {n: params[n][i] for n in _ATTN}
            hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
            q = rms_norm((a @ p["q_w"]).reshape(b, s, hq, d), p["q_norm"],
                         eps)
            k = rms_norm((a @ p["k_w"]).reshape(b, s, hkv, d), p["k_norm"],
                         eps)
            v = (a @ p["v_w"]).reshape(b, s, hkv, d)
            q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
            o, extra = cache_fn(q, k, v)
            y = o.reshape(b, s, hq * d) @ p["o_w"]
        h = h + y
        m = rms_norm(h, params["ffn_norm"][l], eps)
        stats = None
        if l < cfg.num_dense_layers:
            f = swiglu(m, *(params[n][l] for n in _DENSE))
        else:
            e = {n: params[n][l - cfg.num_dense_layers] for n in _MOE}
            routed, stats = held_experts_arrays(
                m.reshape(b * s, hidden), e["router_w"], e["expert_bias"],
                (e["exp_gate_w"], e["exp_up_w"], e["exp_down_w"]),
                cfg.first_expert, cfg.num_experts, cfg.num_experts_per_tok,
                cfg.routed_scaling_factor,
                valid=None if valid is None else jnp.repeat(valid, s),
                scope="lfm2", norm_eps=1e-6)
            f = routed.astype(h.dtype).reshape(b, s, hidden)
        return h + f, extra, stats

    def logits(self, params, h):
        hn = rms_norm(h, params["embedding_norm"], self.cfg.norm_eps)
        return jnp.einsum("bsh,vh->bsv", hn, params["embed"])

    def last_logits(self, params, h):
        # the norm and the head are per position: take the row first
        return self.logits(params, h[:, -1:])[:, 0]


class Lfm2MoeForCausalLM(Layer):
    """The lfm2_moe decoder, a layer's weights at a time."""

    def __init__(self, cfg: Lfm2MoeConfig):
        super().__init__()
        self.cfg = cfg
        L, H = cfg.num_hidden_layers, cfg.hidden_size
        lc, la = cfg.layer_types.count(CONV), cfg.layer_types.count(FULL)
        ld = cfg.num_dense_layers
        lm = L - ld
        hq = cfg.num_attention_heads * cfg.head_dim
        hkv = cfg.num_key_value_heads * cfg.head_dim
        i_d, i_m = cfg.intermediate_size, cfg.moe_intermediate_size
        n, e = cfg.num_experts, cfg.router_experts
        # name -> (how many layers hold one, its shape); None: the model's own.
        # `benchmark/lib/family.weight_rule` makes a name with "norm" 1 and
        # one with "bias" 0: no matrix's name holds either
        shapes = {
            "embed": (None, [cfg.vocab_size, H]),
            "embedding_norm": (None, [H]),
            "operator_norm": (L, [H]), "ffn_norm": (L, [H]),
            "conv_in_w": (lc, [H, 3 * H]),
            "conv_w": (lc, [H, cfg.conv_L_cache]),
            "conv_out_w": (lc, [H, H]),
            "q_w": (la, [H, hq]), "k_w": (la, [H, hkv]),
            "v_w": (la, [H, hkv]), "o_w": (la, [hq, H]),
            "q_norm": (la, [cfg.head_dim]), "k_norm": (la, [cfg.head_dim]),
            "dense_gate_w": (ld, [H, i_d]), "dense_up_w": (ld, [H, i_d]),
            "dense_down_w": (ld, [i_d, H]),
            "router_w": (lm, [H, e]), "expert_bias": (lm, [e]),
            "exp_gate_w": (lm, [n, H, i_m]), "exp_up_w": (lm, [n, H, i_m]),
            "exp_down_w": (lm, [n, i_m, H]),
        }
        normal = Normal(std=cfg.initializer_range)
        self._weights = {}      # name -> Parameter, or a list of them
        for name, (count, shape) in shapes.items():
            init = (Constant(1.0) if name.endswith("_norm")
                    else Constant(0.0) if name == "expert_bias" else normal)
            own = count is None
            made = [self.create_parameter(shape=shape,
                                          default_initializer=init)
                    for _ in range(1 if own else count)]
            for i, p in enumerate(made):
                setattr(self, name if own else f"{name}_{i}", p)
            self._weights[name] = made[0] if own else made
        self._form = None

    def param_arrays(self) -> dict:
        """name -> array, or one array a layer of the weight's kind."""
        return {n: [p._data for p in w] if isinstance(w, list) else w._data
                for n, w in self._weights.items()}

    def serving_form(self) -> Lfm2ServingForm:
        if self._form is None:
            self._form = Lfm2ServingForm(self)
        return self._form

    def forward_arrays(self, params, ids):
        """Array level: logits [B, S, V] of whole sequences `ids` under
        `params` (`param_arrays()`): the serving form's layers over flash
        attention and a convolution that starts from zeros, no cache.
        Pass the weights as ARGUMENTS of a `jax.jit`, not closed over."""
        from ..ops.pallas_ops import flash_attention_arrays

        form = self.serving_form()
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        h = form.embed(params, ids, pos)

        def attn_fn(q, k, v):
            return flash_attention_arrays(q, k, v, is_causal=True), None

        def state_fn(spec):
            def fn(step):
                zeros = jnp.zeros((ids.shape[0],) + tuple(spec.shape),
                                  h.dtype)
                return step(zeros)[0], None
            return fn

        for l, spec in enumerate(form.layer_specs):
            h, _, _ = form.layer(
                l, params, h, pos,
                state_fn(spec) if isinstance(spec, StateSpec) else attn_fn)
        return form.logits(params, h)

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences.  Forward only."""
        tensors, tree = jax.tree_util.tree_flatten(
            self._weights, is_leaf=lambda x: not isinstance(x, (list, dict)))

        def fn(ids, *flat):
            return self.forward_arrays(
                jax.tree_util.tree_unflatten(tree, flat), ids)

        return apply(fn, input_ids, *tensors, name="lfm2_forward")
