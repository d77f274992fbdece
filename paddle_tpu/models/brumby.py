"""brumby — Manifest AI's Brumby-14B-Base (`model_type: brumby`): the
Qwen3-14B shape (40 layers of hidden 5120, 40 query heads over 8 K/V heads
of 128, SwiGLU MLP of 17,408, untied head over 151,936) with every
attention layer replaced by a POWER RETENTION layer ("Scaling Context
Requires Rethinking Attention", arXiv:2507.04239), so the model keeps no
key and no value of any position: its memory of a sequence is one matrix
state a K/V head a layer, of fixed size whatever the sequence's length.

The block (`benchmark/lib/reference_brumby.py` is the independent float32
statement of the same equations, in the attention form):

    a      = RMSNorm(x; in_norm)
    q,k,v  = a Wq [T,Hq,d],  a Wk [T,Hkv,d],  a Wv [T,Hkv,d]     (no bias)
    log g  = logsigmoid(a Wg) [T,Hkv], float32        one gate a K/V head
    q, k   = RMSNorm over each head's d lanes, then rotary positions
             (rotate-half over the whole head, rope_theta)
    b_t    = sum_{r<=t} log g_r
    w_ts   = exp(b_t - b_s) * (q_t . k_s)^2 ,  s <= t
    o_t    = sum_s w_ts v_s / sum_s w_ts            query head h reads K/V
                                                    head h // (Hq / Hkv)
    y      = x + concat_h(o) Wo
    out    = y + (silu(n Wgate) * (n Wup)) Wdown,   n = RMSNorm(y; post_norm)

then a final RMSNorm and the head.  What `config.json` does not carry -
the degree, the gate's form, the normalisation, the kept per-head norms
and rotary positions, the float32 state - is the benchmark
configuration's `assumed` (benchmark/configs/brumby-14b-l6.json).

The same function as a recurrence, the state it keeps and the two kernels
that serve it: `ops/power_retention.py`.  `serving_form()` names one
`StateSpec` a layer, all in the state group "retention" and none of them
an attention layer: the engine admits, schedules and frees such a model by
slots alone, and hands each layer its pool and the rows' slot indices
(`StateSpec.in_place`), so a step moves a live state once in and once out.

Weights are kept a layer at a time (`params()[name][l]`), as models/afmoe.py
says why.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..core.dispatch import apply
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer
from ..ops.power_retention import (retention_decode, retention_prefill,
                                   state_shape)
from .afmoe import rms_norm, rope, swiglu
from .serving_form import ServingForm, StateSpec

__all__ = ["BrumbyConfig", "BrumbyForCausalLM", "brumby_test_config"]


@dataclasses.dataclass
class BrumbyConfig:
    """The published `config.json` keys under their own names, and the
    three the config leaves to the family's description."""

    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_hidden_layers: int = 40
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    hidden_act: str = "silu"
    attention_bias: bool = False
    max_position_embeddings: int = 32768
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    # the Qwen3 shape's window: published switched off (`sliding_window`
    # null, `max_window_layers` 40 beside it are then inert and no field)
    use_sliding_window: bool = False
    model_type: str = "brumby"
    # not keys of config.json
    retention_degree: int = 2
    state_dtype: str = "float32"
    initializer_range: float = 0.02

    def __post_init__(self):
        stated = {"hidden_act": "silu", "attention_bias": False,
                  "rope_scaling": None, "tie_word_embeddings": False,
                  "use_sliding_window": False, "retention_degree": 2,
                  "state_dtype": "float32"}
        for key, want in stated.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"brumby is implemented for {key}={want!r}; got "
                    f"{getattr(self, key)!r}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        if self.head_dim % 2:
            raise ValueError("head_dim must be even")


def brumby_test_config(**kw):
    """The CPU tests' size: 3 layers, 4 query heads over 2 of 16."""
    base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                intermediate_size=128, max_position_embeddings=256)
    base.update(kw)
    return BrumbyConfig(**base)


_LAYER = ("in_norm", "post_norm", "q_w", "k_w", "v_w", "g_w", "o_w",
          "q_norm", "k_norm", "mlp_gate_w", "mlp_up_w", "mlp_down_w")


class BrumbyServingForm(ServingForm):
    """What `LLMEngine` runs of a `BrumbyForCausalLM`."""

    # positions x layers taken through the retention kernels, by the kind
    # of step (the engine labels `phase`)
    stat_counters = (("serving/retention_tokens", {}),)
    # each needs K/V blocks, or a state to roll back or to find at a
    # prefix's end: none is built (ROADMAP R-M11, R-M12)
    unsupported = ("enable_prefix_caching", "speculative_tokens",
                   "kv_cache_dtype")

    def __init__(self, model):
        cfg = model.cfg
        self.model, self.cfg = model, cfg
        self.vocab_size = cfg.vocab_size
        self.max_position_embeddings = cfg.max_position_embeddings
        spec = StateSpec(
            state_shape(cfg.num_key_value_heads, cfg.head_dim), "retention",
            dtype=jnp.dtype(cfg.state_dtype), in_place=True)
        self.layer_specs = [spec] * cfg.num_hidden_layers

    @property
    def dtype(self):
        return self.model.embed.dtype

    def params(self):
        return self.model.param_arrays()

    def embed(self, params, ids, pos):
        return jnp.take(params["embed"], ids, axis=0)

    def layer(self, l, params, h, pos, state_fn, valid=None):
        """`state_fn` is the engine's for an in-place `StateSpec`
        (`ServingForm.layer`): it calls `step(pool, rows, fresh)` once."""
        cfg = self.cfg
        eps = cfg.rms_norm_eps
        b, s, _ = h.shape
        hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        p = {n: params[n][l] for n in _LAYER}
        a = rms_norm(h, p["in_norm"], eps)
        q = rms_norm((a @ p["q_w"]).reshape(b, s, hq, d), p["q_norm"], eps)
        k = rms_norm((a @ p["k_w"]).reshape(b, s, hkv, d), p["k_norm"], eps)
        v = (a @ p["v_w"]).reshape(b, s, hkv, d)
        log_g = jax.nn.log_sigmoid((a @ p["g_w"]).astype(jnp.float32))
        q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)

        def step(pool, rows, fresh):
            if s == 1:
                # one position a row: a decode step, or a prompt of one
                # token, whose slot was zeroed when it was taken
                with jax.named_scope("retention/decode"):
                    o, pool = retention_decode(
                        q[:, 0], k[:, 0], v[:, 0], log_g[:, 0], pool, rows,
                        valid)
                return o[:, None], pool
            with jax.named_scope("retention/prefill"):
                return retention_prefill(q, k, v, log_g, pool, rows, fresh)

        o, extra = state_fn(step)
        h = h + o.astype(h.dtype).reshape(b, s, hq * d) @ p["o_w"]
        m = rms_norm(h, p["post_norm"], eps)
        h = h + swiglu(m, p["mlp_gate_w"], p["mlp_up_w"], p["mlp_down_w"])
        taken = (jnp.int32(b * s) if valid is None
                 else valid.sum().astype(jnp.int32) * s)
        return h, extra, taken[None]

    def logits(self, params, h):
        hn = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.einsum("bsh,vh->bsv", hn, params["head"])

    def last_logits(self, params, h):
        # the norm and the head are per position: take the row first
        return self.logits(params, h[:, -1:])[:, 0]


class BrumbyForCausalLM(Layer):
    """The brumby decoder, a layer's weights at a time."""

    def __init__(self, cfg: BrumbyConfig):
        super().__init__()
        self.cfg = cfg
        L, H, I = (cfg.num_hidden_layers, cfg.hidden_size,
                   cfg.intermediate_size)
        hq = cfg.num_attention_heads * cfg.head_dim
        hkv = cfg.num_key_value_heads * cfg.head_dim
        # name -> (how many layers hold one, its shape); None: the model's
        # own.  `benchmark/lib/family.weight_rule` makes a name with "norm"
        # 1 and every other a matrix of N(0, initializer_range): the
        # gate's projection is `g_w`
        shapes = {
            "embed": (None, [cfg.vocab_size, H]),
            "head": (None, [cfg.vocab_size, H]),
            "final_norm": (None, [H]),
            "in_norm": (L, [H]), "post_norm": (L, [H]),
            "q_w": (L, [H, hq]), "k_w": (L, [H, hkv]), "v_w": (L, [H, hkv]),
            "g_w": (L, [H, cfg.num_key_value_heads]), "o_w": (L, [hq, H]),
            "q_norm": (L, [cfg.head_dim]), "k_norm": (L, [cfg.head_dim]),
            "mlp_gate_w": (L, [H, I]), "mlp_up_w": (L, [H, I]),
            "mlp_down_w": (L, [I, H]),
        }
        normal = Normal(std=cfg.initializer_range)
        self._weights = {}      # name -> Parameter, or a list of them
        for name, (count, shape) in shapes.items():
            init = Constant(1.0) if name.endswith("_norm") else normal
            own = count is None
            made = [self.create_parameter(shape=shape,
                                          default_initializer=init)
                    for _ in range(1 if own else count)]
            for i, p in enumerate(made):
                setattr(self, name if own else f"{name}_{i}", p)
            self._weights[name] = made[0] if own else made
        self._form = None

    def param_arrays(self) -> dict:
        """name -> array, or one array a layer."""
        return {n: [p._data for p in w] if isinstance(w, list) else w._data
                for n, w in self._weights.items()}

    def serving_form(self) -> BrumbyServingForm:
        if self._form is None:
            self._form = BrumbyServingForm(self)
        return self._form

    def forward_arrays(self, params, ids):
        """Array level: logits [B, S, V] of whole sequences `ids` under
        `params` (`param_arrays()`): the serving form's layers, each row
        through the chunked form from a zero state in a pool of its own
        that is dropped afterwards.  Pass the weights as ARGUMENTS of a
        `jax.jit`, not closed over."""
        form = self.serving_form()
        b, s = ids.shape
        pos = jnp.arange(s, dtype=jnp.int32)
        h = form.embed(params, ids, pos)
        rows = jnp.arange(b, dtype=jnp.int32)

        def state_fn(spec):
            def fn(step):
                pool = jnp.zeros((b,) + tuple(spec.shape), spec.dtype)
                return step(pool, rows, True)[0], None
            return fn

        for l, spec in enumerate(form.layer_specs):
            h, _, _ = form.layer(l, params, h, pos, state_fn(spec))
        return form.logits(params, h)

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences.  Forward only."""
        tensors, tree = jax.tree_util.tree_flatten(
            self._weights, is_leaf=lambda x: not isinstance(x, (list, dict)))

        def fn(ids, *flat):
            return self.forward_arrays(
                jax.tree_util.tree_unflatten(tree, flat), ids)

        return apply(fn, input_ids, *tensors, name="brumby_forward")
