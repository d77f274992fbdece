"""afmoe — the Arcee Trinity decoder family (`model_type: afmoe`), in the
form one chip of an expert-parallel deployment serves.

The block (Hugging Face `modeling_afmoe.py`; `benchmark/lib/
reference_afmoe.py` is the independent float32 statement of the same
equations): muP-scaled embedding; RMSNorm before AND after each half of a
block; grouped-query attention (Hq query heads over Hkv K/V heads, head h
reads K/V head h // (Hq/Hkv)) with RMSNorm over every q and k head;
rotary positions on the sliding-window layers only - a full layer sees no
positions; a sigmoid gate on the attention output; SwiGLU MLP in the
`num_dense_layers` leading layers, then a sigmoid-routed top-k mixture of
SwiGLU experts beside a shared expert; final RMSNorm, untied head.

One chip's share: `num_experts` counts the experts HELD here (ids
`first_expert ..`), `router_experts` the router's published width; the
expert layer (`parallel.moe.held_experts_arrays`) routes over all of them
and adds what its own give.  `vocab_size` is the slice of the vocabulary
held here.  Uncut, `router_experts == num_experts`.

Weights are kept a layer at a time (`params()[name][l]`; the dense and the
expert MLPs indexed over their own layers): the engine's programs unroll
the layers, and a slice of a stacked `[L, ...]` array is a copy there - of
576 MB for each of an expert layer's three matrices, which did not fit
(PERF.md, PR 28).
`serving_form()` is what the engine runs (`models.serving_form`);
`forward(ids)` runs the same layer function over whole sequences without a
cache.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import apply
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer
from ..parallel.moe import held_experts_arrays
from .serving_form import LayerSpec, ServingForm

__all__ = ["AfmoeConfig", "AfmoeForCausalLM", "afmoe_test_config"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class AfmoeConfig:
    """The published `config.json` keys under their own names, plus the
    three that say which share of a deployment this is."""

    vocab_size: int = 200192
    hidden_size: int = 3072
    num_hidden_layers: int = 60
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 12288
    moe_intermediate_size: int = 3072
    num_experts: int = 256              # experts held here
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    num_dense_layers: int = 6
    sliding_window: int = 4096
    global_attn_every_n_layers: int = 4
    layer_types: Optional[list] = None  # default: every n-th layer full
    rope_theta: float = 10000.0
    rope_scaling: Optional[dict] = None
    rms_norm_eps: float = 1e-5
    route_scale: float = 2.448
    route_norm: bool = True
    score_func: str = "sigmoid"
    hidden_act: str = "silu"
    mup_enabled: bool = True
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    # routing groups of the published config: all 1, which is no grouping
    n_group: int = 1
    topk_group: int = 1
    num_expert_groups: int = 1
    num_limited_groups: int = 1
    load_balance_coeff: float = 5e-5    # training's; unused when serving
    use_grouped_mm: bool = True
    model_type: str = "afmoe"
    # this chip's share
    router_experts: Optional[int] = None   # router width; default num_experts
    first_expert: int = 0

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = [FULL if (i + 1) % n == 0 else SLIDING
                                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)
        if self.router_experts is None:
            self.router_experts = self.num_experts
        stated = {"score_func": "sigmoid", "hidden_act": "silu",
                  "route_norm": True, "mup_enabled": True,
                  "tie_word_embeddings": False, "rope_scaling": None,
                  "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
                  "num_limited_groups": 1}
        for key, want in stated.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"afmoe is implemented for {key}={want!r}; got "
                    f"{getattr(self, key)!r}")
        if len(self.layer_types) != self.num_hidden_layers or any(
                t not in (SLIDING, FULL) for t in self.layer_types):
            raise ValueError(
                f"layer_types must name {self.num_hidden_layers} layers, "
                f"each {SLIDING!r} or {FULL!r}")
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


def afmoe_test_config(**kw):
    """The CPU tests' size: 1 dense + 5 expert layers, 8 experts top-2."""
    base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=6,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                intermediate_size=128, moe_intermediate_size=32,
                num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
                sliding_window=8, global_attn_every_n_layers=4,
                max_position_embeddings=256)
    base.update(kw)
    return AfmoeConfig(**base)


# -- the block, at array level ----------------------------------------------

def rms_norm(x, w, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * w


def rope(x, pos, theta, inv=None):
    """Rotate-half over the whole head.  x [B,S,heads,D]; pos [B,S], or
    [S] where every row sits at the same positions.  `inv` [D/2]: the
    pairs' frequencies where they are not `theta`'s plain ones."""
    d = x.shape[-1]
    if inv is None:
        inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[..., None] * inv              # [..,S,D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[..., None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[..., None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    return (xf * cos + jnp.concatenate([-x2, x1], -1) * sin).astype(x.dtype)


def swiglu(x, wg, wu, wd):
    a = jax.nn.silu((x @ wg).astype(jnp.float32)) \
        * (x @ wu).astype(jnp.float32)
    return a.astype(x.dtype) @ wd


_ATTN = ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm",
         "q_w", "k_w", "v_w", "gate_w", "o_w", "q_norm", "k_norm")
_DENSE = ("dense_gate_w", "dense_up_w", "dense_down_w")
_MOE = ("router_w", "expert_bias", "exp_gate_w", "exp_up_w", "exp_down_w",
        "shared_gate_w", "shared_up_w", "shared_down_w")


class AfmoeServingForm(ServingForm):
    """What `LLMEngine` runs of an `AfmoeForCausalLM`."""

    # `held_experts_arrays`' four counts
    stat_counters = (("serving/moe_pairs", {"where": "held"}),
                     ("serving/moe_pairs", {"where": "absent"}),
                     ("serving/moe_experts_touched", {}),
                     ("serving/moe_tokens", {}))
    # engine options this family does not carry yet
    unsupported = ("kv_cache_dtype", "speculative_tokens",
                   "enable_prefix_caching")

    def __init__(self, model):
        cfg = model.cfg
        self.model, self.cfg = model, cfg
        self.vocab_size = cfg.vocab_size
        self.max_position_embeddings = cfg.max_position_embeddings
        self.layer_specs = [
            LayerSpec(cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim,
                      cfg.sliding_window if t == SLIDING else None,
                      "window" if t == SLIDING else "full")
            for t in cfg.layer_types]

    @property
    def dtype(self):
        return self.model.embed.dtype

    def params(self):
        return self.model.param_arrays()

    def embed(self, params, ids, pos):
        x = jnp.take(params["embed"], ids, axis=0)
        # in float32: sqrt(3072) is not a bfloat16 number
        return (x.astype(jnp.float32)
                * math.sqrt(self.cfg.hidden_size)).astype(x.dtype)

    def layer(self, l, params, h, pos, attn_fn, valid=None):
        cfg = self.cfg
        eps = cfg.rms_norm_eps
        b, s, hidden = h.shape
        hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        p = {n: params[n][l] for n in _ATTN}
        a = rms_norm(h, p["in_norm"], eps)
        q = rms_norm((a @ p["q_w"]).reshape(b, s, hq, d), p["q_norm"], eps)
        k = rms_norm((a @ p["k_w"]).reshape(b, s, hkv, d), p["k_norm"], eps)
        v = (a @ p["v_w"]).reshape(b, s, hkv, d)
        if cfg.layer_types[l] == SLIDING:   # full layers: no positions
            q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
        o, extra = attn_fn(q, k, v)
        gate = jax.nn.sigmoid((a @ p["gate_w"]).astype(jnp.float32))
        o = (o.reshape(b, s, hq * d).astype(jnp.float32) * gate).astype(
            h.dtype)
        h = h + rms_norm(o @ p["o_w"], p["post_attn_norm"], eps)
        m = rms_norm(h, p["pre_mlp_norm"], eps)
        stats = None
        if l < cfg.num_dense_layers:
            f = swiglu(m, *(params[n][l] for n in _DENSE))
        else:
            e = {n: params[n][l - cfg.num_dense_layers] for n in _MOE}
            flat = m.reshape(b * s, hidden)
            routed, stats = held_experts_arrays(
                flat, e["router_w"], e["expert_bias"],
                (e["exp_gate_w"], e["exp_up_w"], e["exp_down_w"]),
                cfg.first_expert, cfg.num_experts, cfg.num_experts_per_tok,
                cfg.route_scale,
                valid=None if valid is None else (
                    valid.reshape(-1) if valid.ndim == 2
                    else jnp.repeat(valid, s)),
                scope="afmoe", norm_eps=1e-20)
            with jax.named_scope("afmoe/shared_expert"):
                shared = swiglu(flat, e["shared_gate_w"], e["shared_up_w"],
                                e["shared_down_w"])
            f = (routed + shared.astype(jnp.float32)).astype(
                h.dtype).reshape(b, s, hidden)
        return h + rms_norm(f, p["post_mlp_norm"], eps), extra, stats

    def logits(self, params, h):
        hn = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.einsum("bsh,vh->bsv", hn, params["head"])

    def last_logits(self, params, h):
        # the norm and the head are per position: take the row first
        return self.logits(params, h[:, -1:])[:, 0]


class AfmoeForCausalLM(Layer):
    """The afmoe decoder (this chip's share of it), a layer's weights at
    a time."""

    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.cfg = cfg
        L, H = cfg.num_hidden_layers, cfg.hidden_size
        ld = cfg.num_dense_layers
        lm = L - ld
        hq = cfg.num_attention_heads * cfg.head_dim
        hkv = cfg.num_key_value_heads * cfg.head_dim
        i_d, i_m = cfg.intermediate_size, cfg.moe_intermediate_size
        i_s = i_m * cfg.num_shared_experts
        n, e = cfg.num_experts, cfg.router_experts
        # name -> (how many layers hold one, its shape); 0: the model's own
        shapes = {
            "embed": (0, [cfg.vocab_size, H]),
            "head": (0, [cfg.vocab_size, H]), "final_norm": (0, [H]),
            "in_norm": (L, [H]), "post_attn_norm": (L, [H]),
            "pre_mlp_norm": (L, [H]), "post_mlp_norm": (L, [H]),
            "q_w": (L, [H, hq]), "k_w": (L, [H, hkv]), "v_w": (L, [H, hkv]),
            "gate_w": (L, [H, hq]), "o_w": (L, [hq, H]),
            "q_norm": (L, [cfg.head_dim]), "k_norm": (L, [cfg.head_dim]),
            "dense_gate_w": (ld, [H, i_d]), "dense_up_w": (ld, [H, i_d]),
            "dense_down_w": (ld, [i_d, H]),
            "router_w": (lm, [H, e]), "expert_bias": (lm, [e]),
            "exp_gate_w": (lm, [n, H, i_m]), "exp_up_w": (lm, [n, H, i_m]),
            "exp_down_w": (lm, [n, i_m, H]),
            "shared_gate_w": (lm, [H, i_s]), "shared_up_w": (lm, [H, i_s]),
            "shared_down_w": (lm, [i_s, H]),
        }
        normal = Normal(std=cfg.initializer_range)
        self._weights = {}      # name -> Parameter, or a list of them
        for name, (count, shape) in shapes.items():
            init = (Constant(1.0) if name.endswith("_norm")
                    else Constant(0.0) if name == "expert_bias" else normal)
            made = [self.create_parameter(shape=shape,
                                          default_initializer=init)
                    for _ in range(max(count, 1))]
            for i, p in enumerate(made):
                setattr(self, f"{name}_{i}" if count else name, p)
            self._weights[name] = made if count else made[0]
        self._form = None

    def param_arrays(self) -> dict:
        """name -> array, or one array a layer."""
        return {n: [p._data for p in w] if isinstance(w, list) else w._data
                for n, w in self._weights.items()}

    def serving_form(self) -> AfmoeServingForm:
        if self._form is None:
            self._form = AfmoeServingForm(self)
        return self._form

    def forward_arrays(self, params, ids):
        """Array level: logits [B, S, V] of whole sequences `ids` under
        `params` (`param_arrays()`): the serving form's layers over flash
        attention (window and grouped heads included), no cache.  Pass the
        weights as ARGUMENTS of a `jax.jit`: closed over, 8.6 GB of them
        become constants of the program."""
        from ..ops.pallas_ops import flash_attention_arrays

        form = self.serving_form()
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        h = form.embed(params, ids, pos)
        for l, spec in enumerate(form.layer_specs):
            h, _, _ = form.layer(
                l, params, h, pos,
                lambda q, k, v, spec=spec: (flash_attention_arrays(
                    q, k, v, is_causal=True, window=spec.window), None))
        return form.logits(params, h)

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences.  Forward only."""
        tensors, tree = jax.tree_util.tree_flatten(
            self._weights, is_leaf=lambda x: not isinstance(x, (list, dict)))

        def fn(ids, *flat):
            return self.forward_arrays(
                jax.tree_util.tree_unflatten(tree, flat), ids)

        return apply(fn, input_ids, *tensors, name="afmoe_forward")
