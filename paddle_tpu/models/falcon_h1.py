"""falcon_h1 — TII's Falcon-H1 hybrid decoder (`model_type: falcon_h1`;
Falcon-H1-34B-Instruct is 72 layers of hidden 5120): every layer runs
grouped-query ATTENTION and a Mamba-2 MIXER side by side on the same normed
input and adds both to the residual, then a SwiGLU MLP.

The block (`benchmark/lib/reference_falcon_h1.py` is the independent
float32 statement of the same equations; each multiplier is a key of
`config.json`):

    x      = RMSNorm(h; in_norm)
    attention (a = x * attention_in_multiplier):
      q, k, v = a Wq [T,Hq,d],  (a Wk) * key_multiplier [T,Hkv,d],  a Wv
      q, k    = rotary positions (rotate-half over the head, rope_theta)
      attn    = causal softmax(q k^T / sqrt d) v  Wo * attention_out_multiplier
                (query head i reads K/V head i // (Hq / Hkv))
    mixer (u = x * ssm_in_multiplier):
      z | xBC | dt = (u W_in) * mup      mup: ssm_multipliers over the five
                                         parts z, x, B, C, dt
      xBC     = SiLU(causal depthwise conv of mamba_d_conv taps + bias)
      x | B | C = xBC                    B, C: mamba_n_groups groups of
                                         mamba_d_state
      dt      = softplus(dt + dt_bias),  A = -exp(A_log)   a head each
      S_t     = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t
                (head h reads group h // (heads / groups))
      ssm     = RMSNormGated(y * SiLU(z)) W_out * ssm_out_multiplier
                (normalised over each of the mamba_n_groups groups)
    h      = h + attn + ssm
    h      = h + [SiLU((n Wg) * mlp_multipliers[0]) * (n Wu)] Wd
                 * mlp_multipliers[1],   n = RMSNorm(h; pre_ff_norm)

the embedding times `embedding_multiplier`, a final RMSNorm and an untied
head times `lm_head_multiplier`.

`A_log`, `dt_bias` and `D` are fixed arrays of the model, not parameters
(`fixed_ssm_arrays`): Mamba-2's initial ranges, so that a model made from a
seed keeps heads whose state lasts hundreds of positions.

`serving_form()` names, for every layer, THREE specs: a `LayerSpec` of
group "full" (the K/V), a gathered `StateSpec` of group "conv" (the last
`mamba_d_conv - 1` inputs of the convolution) and an in-place `StateSpec`
of group "ssm" (the `[heads, d_state, d_head]` float32 state,
`ops/mamba2_ssd.py`), and its layer step takes the engine's function for
each, in that order.  Weights are kept a layer at a time
(`params()[name][l]`), as models/afmoe.py says why.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import apply
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer
from ..ops.mamba2_ssd import ssd_decode, ssd_prefill, state_shape
from .afmoe import rms_norm, rope
from .lfm2 import short_conv
from .serving_form import LayerSpec, ServingForm, StateSpec

__all__ = ["FalconH1Config", "FalconH1ForCausalLM", "falcon_h1_test_config",
           "fixed_ssm_arrays"]


@dataclasses.dataclass
class FalconH1Config:
    """The published `config.json` keys under their own names (the 34B
    model's values) but three that change nothing here (`mamba_expand`,
    which `mamba_d_ssm` overrides, `mlp_expansion_factor`, which
    `intermediate_size` states, `num_logits_to_keep`), and two it leaves to
    the family's code."""

    vocab_size: int = 261120
    hidden_size: int = 5120
    intermediate_size: int = 21504
    num_hidden_layers: int = 72
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    attention_bias: bool = False
    attn_layer_indices: Optional[list] = None
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    rope_scaling: Optional[dict] = None
    tie_word_embeddings: bool = False
    mamba_chunk_size: int = 128
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 128
    mamba_d_ssm: int = 4096
    mamba_d_state: int = 256
    mamba_n_groups: int = 2
    mamba_n_heads: int = 32
    mamba_norm_before_gate: bool = False
    mamba_proj_bias: bool = False
    mamba_rms_norm: bool = True
    mamba_use_mlp: bool = True
    mlp_bias: bool = False
    projectors_bias: bool = False
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    key_multiplier: float = 0.011048543456039804
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    model_type: str = "falcon_h1"
    # not keys of config.json
    state_dtype: str = "float32"
    initializer_range: float = 0.02
    # the ranges `fixed_ssm_arrays` lays over the mixer heads (Mamba2Config's
    # names; D is set, not drawn)
    A_initializer_range: tuple = (1.0, 16.0)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    D_initial: float = 1.0

    def __post_init__(self):
        stated = {"hidden_act": "silu", "attention_bias": False,
                  "attn_layer_indices": None, "rope_scaling": None,
                  "tie_word_embeddings": False, "mamba_conv_bias": True,
                  "mamba_norm_before_gate": False, "mamba_proj_bias": False,
                  "mamba_rms_norm": True, "mamba_use_mlp": True,
                  "mlp_bias": False, "projectors_bias": False,
                  "state_dtype": "float32"}
        for key, want in stated.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"falcon_h1 is implemented for {key}={want!r}; got "
                    f"{getattr(self, key)!r}")
        # a configuration file states some of these as whole numbers
        for key in ("rope_theta", "embedding_multiplier",
                    "lm_head_multiplier", "attention_in_multiplier",
                    "attention_out_multiplier", "key_multiplier",
                    "ssm_in_multiplier", "ssm_out_multiplier"):
            setattr(self, key, float(getattr(self, key)))
        self.ssm_multipliers = tuple(float(m) for m in self.ssm_multipliers)
        self.mlp_multipliers = tuple(float(m) for m in self.mlp_multipliers)
        self.A_initializer_range = tuple(
            float(a) for a in self.A_initializer_range)
        for key in ("time_step_min", "time_step_max", "D_initial"):
            setattr(self, key, float(getattr(self, key)))
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers names z, x, B, C and dt; "
                             "mlp_multipliers the gate and the down product")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        # `mamba_d_ssm` overrides `mamba_expand * hidden_size`
        if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
            raise ValueError("mamba_d_ssm must be mamba_n_heads x "
                             "mamba_d_head")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_heads must be a multiple of "
                             "mamba_n_groups")
        if self.mamba_d_conv < 2 or self.head_dim % 2:
            raise ValueError("mamba_d_conv must be at least 2 and head_dim "
                             "even")

    @property
    def conv_dim(self) -> int:
        """The convolution's channels: x, then B and C of every group."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state


def falcon_h1_test_config(**kw):
    """The CPU tests' size: 2 layers, 4 query heads over 2 of 16, a mixer
    of 4 heads of 16 over 2 groups of a 16-wide state, chunks of 16."""
    base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                intermediate_size=128, mamba_n_heads=4, mamba_d_head=16,
                mamba_d_ssm=64, mamba_d_state=16, mamba_n_groups=2,
                mamba_chunk_size=16, max_position_embeddings=512)
    base.update(kw)
    return FalconH1Config(**base)


def fixed_ssm_arrays(cfg):
    """(A_log, dt_bias, D), one number a mixer head, float32: A spread over
    `A_initializer_range` ([1, 16]), dt log-spaced over [`time_step_min`,
    `time_step_max`] ([0.001, 0.1]) with `dt_bias` its inverse softplus (so
    a zero input gives that dt), D `D_initial` (1) - Mamba-2's initial
    ranges."""
    n = cfg.mamba_n_heads
    a = np.linspace(*cfg.A_initializer_range, n)
    dt = np.exp(np.linspace(np.log(cfg.time_step_min),
                            np.log(cfg.time_step_max), n))
    return (jnp.asarray(np.log(a), jnp.float32),
            jnp.asarray(dt + np.log(-np.expm1(-dt)), jnp.float32),
            jnp.full((n,), cfg.D_initial, jnp.float32))


def mup_vector(cfg):
    """`ssm_multipliers` laid over the in-projection's five parts."""
    gn = cfg.mamba_n_groups * cfg.mamba_d_state
    sizes = (cfg.mamba_d_ssm, cfg.mamba_d_ssm, gn, gn, cfg.mamba_n_heads)
    return jnp.concatenate([jnp.full((n,), m, jnp.float32)
                            for n, m in zip(sizes, cfg.ssm_multipliers)])


def gated_group_norm(y, z, w, groups, eps):
    """RMSNormGated with the gate first: `y * SiLU(z)`, normalised over
    each of `groups` equal parts of the last axis, times `w`.  float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    parts = g.reshape(g.shape[:-1] + (groups, -1))
    parts = parts * jax.lax.rsqrt(
        jnp.mean(parts * parts, axis=-1, keepdims=True) + eps)
    return parts.reshape(g.shape) * w.astype(jnp.float32)


_LAYER = ("in_norm", "pre_ff_norm", "q_w", "k_w", "v_w", "o_w", "in_proj_w",
          "conv_w", "conv_bias", "gate_norm", "out_proj_w", "mlp_gate_w",
          "mlp_up_w", "mlp_down_w", "A_log", "dt_bias", "D")


class FalconH1ServingForm(ServingForm):
    """What `LLMEngine` runs of a `FalconH1ForCausalLM`."""

    # positions x layers taken through the SSD kernels, by the kind of
    # step (the engine labels `phase`)
    stat_counters = (("serving/ssd_tokens", {}),)
    # each needs a state to roll back or to find at a prefix's end, or the
    # grouped kernel over int8 pools: none is built (ROADMAP R-M14)
    unsupported = ("enable_prefix_caching", "speculative_tokens",
                   "kv_cache_dtype")

    def __init__(self, model):
        cfg = model.cfg
        self.model, self.cfg = model, cfg
        self.vocab_size = cfg.vocab_size
        self.max_position_embeddings = cfg.max_position_embeddings
        self._mup = mup_vector(cfg)
        specs = (
            LayerSpec(cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim, None, "full"),
            StateSpec((cfg.mamba_d_conv - 1, cfg.conv_dim), "conv"),
            StateSpec(state_shape(cfg.mamba_n_heads, cfg.mamba_d_head,
                                  cfg.mamba_d_state), "ssm",
                      dtype=jnp.dtype(cfg.state_dtype), in_place=True))
        self.layer_specs = [specs] * cfg.num_hidden_layers

    @property
    def dtype(self):
        return self.model.embed.dtype

    def params(self):
        return self.model.param_arrays()

    def embed(self, params, ids, pos):
        e = jnp.take(params["embed"], ids, axis=0)
        return (e.astype(jnp.float32)
                * self.cfg.embedding_multiplier).astype(e.dtype)

    def layer(self, l, params, h, pos, attn_fn, conv_fn, ssm_fn,
              valid=None):
        """`attn_fn`, `conv_fn`, `ssm_fn`: the engine's functions for the
        layer's three specs (`ServingForm.layer`)."""
        cfg = self.cfg
        f32 = jnp.float32
        eps = cfg.rms_norm_eps
        b, s, _ = h.shape
        dtype = h.dtype
        hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        nh, dh, ng = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups
        gn = ng * cfg.mamba_d_state
        p = {n: params[n][l] for n in _LAYER}
        x = rms_norm(h, p["in_norm"], eps)

        a = x if cfg.attention_in_multiplier == 1 else (
            x.astype(f32) * cfg.attention_in_multiplier).astype(dtype)
        q = (a @ p["q_w"]).reshape(b, s, hq, d)
        k = ((a @ p["k_w"]).astype(f32)
             * cfg.key_multiplier).astype(dtype).reshape(b, s, hkv, d)
        v = (a @ p["v_w"]).reshape(b, s, hkv, d)
        q, k = rope(q, pos, cfg.rope_theta), rope(k, pos, cfg.rope_theta)
        o, attn_extra = attn_fn(q, k, v)
        attn = (o.reshape(b, s, hq * d) @ p["o_w"]).astype(f32) \
            * cfg.attention_out_multiplier

        with jax.named_scope("mamba2/in_proj"):
            u = (x.astype(f32) * cfg.ssm_in_multiplier).astype(dtype)
            zxbcdt = (u @ p["in_proj_w"]).astype(f32) * self._mup
            z, xbc, dt = jnp.split(
                zxbcdt, [cfg.mamba_d_ssm, cfg.mamba_d_ssm + cfg.conv_dim],
                axis=-1)
        with jax.named_scope("mamba2/conv"):
            c, conv_extra = conv_fn(
                lambda prev: short_conv(xbc, prev, p["conv_w"]))
            xbc = jax.nn.silu(c.astype(f32) + p["conv_bias"].astype(f32))
            xs, bb, cc = jnp.split(
                xbc.astype(dtype), [cfg.mamba_d_ssm, cfg.mamba_d_ssm + gn],
                axis=-1)
            xs = xs.reshape(b, s, nh, dh)
            bb = bb.reshape(b, s, ng, cfg.mamba_d_state)
            cc = cc.reshape(b, s, ng, cfg.mamba_d_state)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        neg_a = -jnp.exp(p["A_log"])

        def step(pool, rows, fresh):
            if s == 1:
                # one position a row: a decode step, or a prompt of one
                # token, whose slot was zeroed when it was taken
                y, pool = ssd_decode(xs[:, 0], dt[:, 0], neg_a, bb[:, 0],
                                     cc[:, 0], pool, rows, valid)
                return y[:, None], pool
            return ssd_prefill(xs, dt, neg_a, bb, cc, pool, rows, fresh,
                               cfg.mamba_chunk_size)

        with jax.named_scope("mamba2/ssd"):
            y, ssm_extra = ssm_fn(step)
        y = y + p["D"][:, None] * xs.astype(f32)
        with jax.named_scope("mamba2/gate_norm"):
            y = gated_group_norm(y.reshape(b, s, cfg.mamba_d_ssm), z,
                                 p["gate_norm"], ng, eps)
        with jax.named_scope("mamba2/out"):
            ssm = (y.astype(dtype) @ p["out_proj_w"]).astype(f32) \
                * cfg.ssm_out_multiplier
        h = (h.astype(f32) + attn + ssm).astype(dtype)

        with jax.named_scope("falcon_h1/mlp"):
            gm, dm = cfg.mlp_multipliers
            n = rms_norm(h, p["pre_ff_norm"], eps)
            g = jax.nn.silu((n @ p["mlp_gate_w"]).astype(f32) * gm) \
                * (n @ p["mlp_up_w"]).astype(f32)
            f = (g.astype(dtype) @ p["mlp_down_w"]).astype(f32) * dm
            h = (h.astype(f32) + f).astype(dtype)
        taken = (jnp.int32(b * s) if valid is None
                 else valid.sum().astype(jnp.int32) * s)
        return h, tuple(attn_extra) + tuple(conv_extra) + tuple(ssm_extra), \
            taken[None]

    def logits(self, params, h):
        hn = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.einsum("bsh,vh->bsv", hn, params["head"]) \
            * self.cfg.lm_head_multiplier

    def last_logits(self, params, h):
        # the norm and the head are per position: take the row first
        return self.logits(params, h[:, -1:])[:, 0]


class FalconH1ForCausalLM(Layer):
    """The falcon_h1 decoder, a layer's weights at a time."""

    def __init__(self, cfg: FalconH1Config):
        super().__init__()
        self.cfg = cfg
        L, H, I = (cfg.num_hidden_layers, cfg.hidden_size,
                   cfg.intermediate_size)
        hq = cfg.num_attention_heads * cfg.head_dim
        hkv = cfg.num_key_value_heads * cfg.head_dim
        proj = 2 * cfg.mamba_d_ssm + 2 * cfg.mamba_n_groups \
            * cfg.mamba_d_state + cfg.mamba_n_heads
        # name -> (how many layers hold one, its shape); None: the model's
        # own.  `benchmark/lib/family.weight_rule` makes a name with "norm"
        # 1, one with "bias" 0 and every other a matrix of
        # N(0, initializer_range)
        shapes = {
            "embed": (None, [cfg.vocab_size, H]),
            "head": (None, [cfg.vocab_size, H]),
            "final_norm": (None, [H]),
            "in_norm": (L, [H]), "pre_ff_norm": (L, [H]),
            "q_w": (L, [H, hq]), "k_w": (L, [H, hkv]), "v_w": (L, [H, hkv]),
            "o_w": (L, [hq, H]),
            "in_proj_w": (L, [H, proj]),
            "conv_w": (L, [cfg.conv_dim, cfg.mamba_d_conv]),
            "conv_bias": (L, [cfg.conv_dim]),
            "gate_norm": (L, [cfg.mamba_d_ssm]),
            "out_proj_w": (L, [cfg.mamba_d_ssm, H]),
            "mlp_gate_w": (L, [H, I]), "mlp_up_w": (L, [H, I]),
            "mlp_down_w": (L, [I, H]),
        }
        normal = Normal(std=cfg.initializer_range)
        self._weights = {}      # name -> Parameter, or a list of them
        for name, (count, shape) in shapes.items():
            init = (Constant(1.0) if "norm" in name
                    else Constant(0.0) if "bias" in name else normal)
            own = count is None
            made = [self.create_parameter(shape=shape,
                                          default_initializer=init)
                    for _ in range(1 if own else count)]
            for i, p in enumerate(made):
                setattr(self, name if own else f"{name}_{i}", p)
            self._weights[name] = made[0] if own else made
        self._fixed = dict(zip(("A_log", "dt_bias", "D"),
                               fixed_ssm_arrays(cfg)))
        self._form = None

    def param_arrays(self) -> dict:
        """name -> array, or one array a layer; `A_log`, `dt_bias` and `D`
        the fixed arrays, the same in every layer."""
        out = {n: [p._data for p in w] if isinstance(w, list) else w._data
               for n, w in self._weights.items()}
        out.update({n: [a] * self.cfg.num_hidden_layers
                    for n, a in self._fixed.items()})
        return out

    def serving_form(self) -> FalconH1ServingForm:
        if self._form is None:
            self._form = FalconH1ServingForm(self)
        return self._form

    def forward_arrays(self, params, ids):
        """Array level: logits [B, S, V] of whole sequences `ids` under
        `params` (`param_arrays()`): the serving form's layers over flash
        attention, a convolution from zeros and the chunked SSD from a zero
        state in a pool of its own that is dropped afterwards.  Pass the
        weights as ARGUMENTS of a `jax.jit`, not closed over."""
        from ..ops.pallas_ops import flash_attention_arrays

        form = self.serving_form()
        b, s = ids.shape
        pos = jnp.arange(s, dtype=jnp.int32)
        h = form.embed(params, ids, pos)
        rows = jnp.arange(b, dtype=jnp.int32)

        def attn_fn(q, k, v):
            return flash_attention_arrays(q, k, v, is_causal=True), ()

        def conv_fn(step):
            spec = form.layer_specs[0][1]
            return step(jnp.zeros((b,) + tuple(spec.shape), h.dtype))[0], ()

        def ssm_fn(step):
            spec = form.layer_specs[0][2]
            pool = jnp.zeros((b,) + tuple(spec.shape), spec.dtype)
            return step(pool, rows, True)[0], ()

        for l in range(self.cfg.num_hidden_layers):
            h, _, _ = form.layer(l, params, h, pos, attn_fn, conv_fn, ssm_fn)
        return form.logits(params, h)

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences.  Forward only."""
        tensors, tree = jax.tree_util.tree_flatten(
            self._weights, is_leaf=lambda x: not isinstance(x, (list, dict)))

        def fn(ids, *flat):
            params = jax.tree_util.tree_unflatten(tree, flat)
            params.update({n: [a] * self.cfg.num_hidden_layers
                           for n, a in self._fixed.items()})
            return self.forward_arrays(params, ids)

        return apply(fn, input_ids, *tensors, name="falcon_h1_forward")
