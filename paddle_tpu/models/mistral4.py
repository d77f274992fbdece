"""mistral4 - the Mistral Small 4 decoder family (`model_type: mistral4`;
Mistral-Small-4-119B-2603 is 36 layers of hidden 4096, every layer
multi-head latent attention and 128 experts top-4 beside a shared one,
about 6.5B of 119B parameters active), the language model on text, in the
form one chip of an expert-parallel deployment serves.

The block (`benchmark/lib/reference_mistral4.py` is the independent
float32 statement of the same equations), pre-norm only, `x_t` the
residual at position t and H heads i:

  h = rmsnorm(x_t)
  c_q = rmsnorm(W_dq h)                      [q_lora_rank]
  q_i = W_uq,i c_q = [q_i^nope | q_i^rope]   [qk_nope_head_dim | qk_rope_head_dim]
  [c_kv | k^rope] = W_dkv h;  c_kv <- rmsnorm(c_kv)
        the row [c_kv | k^rope], kv_lora_rank + qk_rope_head_dim numbers,
        is ALL the cache keeps of a token
  [k_i^nope | v_i] = W_ukv,i c_kv            per head, from the latent
  q_i^rope, k^rope <- RoPE_t (YaRN frequencies, interleaved pairs);
        k^rope is one vector shared by the heads
  s_ij = a_t * scale * (q_i^nope . k_ij^nope + q_i^rope . k_j^rope), j <= t
        a_t = 1 + beta * ln(1 + floor(t / original_max_position_embeddings))
  o_i = sum_j softmax_j(s_ij) v_ij;  x <- x + W_o [o_1 .. o_H]
  m = rmsnorm(x);  sigmoid-routed top-k of `n_routed_experts` SwiGLU
  experts (weights the selected scores over their sum, times
  `routed_scaling_factor`) beside `n_shared_experts` shared ones
  final RMSNorm, untied head.

The two forms of the attention (`models.serving_form.LatentSpec`).  A
whole-prompt prefill EXPANDS: per-head keys and values from the chunk's
own latents, through the flash kernel.  Every program that reads the
cache ABSORBS: with `W_ukv,i = [W_uk,i ; W_uv,i]`, `q~_i = [W_uk,i^T
q_i^nope | q_i^rope]` scores against the stored row itself, `u_i = sum_j
p_ij c_kv,j` sums its leading `kv_lora_rank` lanes, and `o_i = W_uv,i
u_i`: decode never builds a key or a value.  `a_t` multiplies the query
(1 exactly below `original_max_position_embeddings`).

One chip's share, as in models/afmoe.py: `n_routed_experts` counts the
experts HELD here (ids `first_expert ..`), `router_experts` the router's
published width, `vocab_size` the slice of the vocabulary held here.

Weights are kept a layer at a time (`params()[name][l]`, PERF.md PR 28);
`forward(ids)` runs the expanded form over whole sequences, no cache.
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
from .afmoe import rms_norm, rope, swiglu
from .serving_form import LatentSpec, ServingForm

__all__ = ["Mistral4Config", "Mistral4ForCausalLM", "mistral4_test_config",
           "yarn_inv_freq", "query_scale"]


@dataclasses.dataclass
class Mistral4Config:
    """The published `config.json` keys under their own names, the one
    size the config leaves to the family's code (`initializer_range`), and
    the two that say which experts live here."""

    vocab_size: int = 131072
    hidden_size: int = 4096
    intermediate_size: int = 12288      # a dense layer's; none is dense
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_head_dim: int = 128
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 128         # experts held here
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    hidden_act: str = "silu"
    attention_bias: bool = False
    mlp_bias: bool = False
    rms_norm_eps: float = 1e-6
    rope_interleave: bool = True
    rope_parameters: Optional[dict] = None
    sliding_window: Optional[int] = None
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 1048576
    initializer_range: float = 0.02
    model_type: str = "mistral4"
    # this chip's share
    router_experts: Optional[int] = None   # router width; default held
    first_expert: int = 0

    def __post_init__(self):
        if self.rope_parameters is None:
            self.rope_parameters = {
                "rope_type": "yarn", "rope_theta": 10000.0, "factor": 128.0,
                "original_max_position_embeddings": 8192, "beta_fast": 32.0,
                "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0,
                "llama_4_scaling_beta": 0.1}
        if self.router_experts is None:
            self.router_experts = self.n_routed_experts
        stated = {"first_k_dense_replace": 0, "n_group": 1, "topk_group": 1,
                  "norm_topk_prob": True, "hidden_act": "silu",
                  "attention_bias": False, "mlp_bias": False,
                  "rope_interleave": True, "sliding_window": None,
                  "tie_word_embeddings": False}
        for key, want in stated.items():
            if getattr(self, key) != want:
                raise ValueError(
                    f"mistral4 is implemented for {key}={want!r}; got "
                    f"{getattr(self, key)!r}")
        rp = self.rope_parameters
        if rp.get("rope_type", rp.get("type")) != "yarn":
            raise ValueError("mistral4 is implemented for YaRN rotary "
                             "positions (rope_type 'yarn')")
        if rp.get("mscale", 1) != rp.get("mscale_all_dim", 1):
            raise ValueError("mistral4 is implemented for mscale == "
                             "mscale_all_dim (a factor of 1 on cos/sin)")
        if self.qk_head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
            raise ValueError("qk_head_dim must be qk_nope_head_dim + "
                             "qk_rope_head_dim")
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even")
        if self.first_expert + self.n_routed_experts > self.router_experts:
            raise ValueError(
                f"experts {self.first_expert}.."
                f"{self.first_expert + self.n_routed_experts - 1} held, but "
                f"the router is {self.router_experts} wide")

    @property
    def softmax_scale(self) -> float:
        """qk_head_dim^-0.5 times the square of YaRN's `0.1 *
        mscale_all_dim * ln(factor) + 1`."""
        rp = self.rope_parameters
        m = 1.0
        if rp.get("mscale_all_dim") and rp["factor"] > 1:
            m = 0.1 * rp["mscale_all_dim"] * math.log(rp["factor"]) + 1.0
        return self.qk_head_dim ** -0.5 * m * m


def mistral4_test_config(**kw):
    """The CPU tests' size: 3 layers, 8 experts top-2, 4 heads of 8 + 8
    over a latent of 24 + 8, positions past a shrunk
    `original_max_position_embeddings` of 32."""
    base = dict(vocab_size=96, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4, head_dim=16,
                q_lora_rank=32, kv_lora_rank=24, qk_head_dim=16,
                qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
                intermediate_size=128, moe_intermediate_size=32,
                n_routed_experts=8, num_experts_per_tok=2,
                max_position_embeddings=256,
                rope_parameters={
                    "rope_type": "yarn", "rope_theta": 10000.0,
                    "factor": 8.0, "original_max_position_embeddings": 32,
                    "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
                    "mscale_all_dim": 1.0, "llama_4_scaling_beta": 0.1})
    base.update(kw)
    return Mistral4Config(**base)


# -- positions ---------------------------------------------------------------

def yarn_inv_freq(dim, rp):
    """The `dim / 2` rotary frequencies under YaRN (`rope_parameters`):
    pair i turns `theta^(-2i/dim)` a position where it turns more than
    `beta_fast` times over the original context (kept), that over `factor`
    where fewer than `beta_slow` (interpolated), a linear ramp between.
    float32 numpy."""
    import numpy as np

    base, factor = float(rp["rope_theta"]), float(rp["factor"])
    orig = float(rp["original_max_position_embeddings"])
    kept = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def pair_of(turns):       # the pair that turns `turns` times over orig
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(pair_of(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(pair_of(float(rp["beta_slow"]))), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (kept / factor * ramp + kept * (1.0 - ramp)).astype(np.float32)


def query_scale(pos, rp):
    """a_t = 1 + beta * ln(1 + floor(t / original_max_position_
    embeddings)): float32, 1 exactly below the original context."""
    whole = jnp.floor_divide(pos, int(rp["original_max_position_embeddings"]))
    return 1.0 + float(rp.get("llama_4_scaling_beta", 0.0)) * jnp.log1p(
        whole.astype(jnp.float32))


def rope_interleaved(x, pos, inv):
    """Rotary positions over INTERLEAVED pairs (2i, 2i + 1) of x [B, S,
    heads, D]: the pairs brought to (i, i + D/2) and rotated there by
    afmoe's routine.  Queries and keys are permuted alike, so their
    products are those of the pairs rotated in place."""
    b, s, h, d = x.shape
    halves = jnp.swapaxes(x.reshape(b, s, h, d // 2, 2), -1, -2)
    return rope(halves.reshape(b, s, h, d), pos, None, inv=inv)


_LAYER = ("in_norm", "q_a_w", "q_a_norm", "q_b_w", "kv_a_w", "kv_a_norm",
          "kv_b_w", "o_w", "ffn_norm", "router_w", "expert_bias",
          "exp_gate_w", "exp_up_w", "exp_down_w", "shared_gate_w",
          "shared_up_w", "shared_down_w")


class Mistral4ServingForm(ServingForm):
    """What `LLMEngine` runs of a `Mistral4ForCausalLM`."""

    # `held_experts_arrays`' four counts, under afmoe's names
    stat_counters = (("serving/moe_pairs", {"where": "held"}),
                     ("serving/moe_pairs", {"where": "absent"}),
                     ("serving/moe_experts_touched", {}),
                     ("serving/moe_tokens", {}))
    # low-bit latent rows, a verify step over the absorbed form at C > 1
    # and block reuse over a latent group are not built (ROADMAP R-M1)
    unsupported = ("kv_cache_dtype", "speculative_tokens",
                   "enable_prefix_caching")
    def __init__(self, model):
        cfg = model.cfg
        self.model, self.cfg = model, cfg
        self.vocab_size = cfg.vocab_size
        self.max_position_embeddings = cfg.max_position_embeddings
        self.layer_specs = [
            LatentSpec(cfg.num_attention_heads,
                       cfg.kv_lora_rank + cfg.qk_rope_head_dim,
                       cfg.kv_lora_rank, cfg.softmax_scale)
        ] * cfg.num_hidden_layers
        self._inv = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_parameters)

    @property
    def dtype(self):
        return self.model.embed.dtype

    def params(self):
        return self.model.param_arrays()

    def embed(self, params, ids, pos):
        return jnp.take(params["embed"], ids, axis=0)

    def layer(self, l, params, h, pos, latent_fn, valid=None):
        cfg = self.cfg
        eps = cfg.rms_norm_eps
        b, s, hidden = h.shape
        nh, dn, dr, dv, rank = (cfg.num_attention_heads,
                                cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                                cfg.v_head_dim, cfg.kv_lora_rank)
        p = {n: params[n][l] for n in _LAYER}
        a = rms_norm(h, p["in_norm"], eps)
        q = (rms_norm(a @ p["q_a_w"], p["q_a_norm"], eps)
             @ p["q_b_w"]).reshape(b, s, nh, dn + dr)
        # a_t on the query: 1 exactly below the original context
        a_t = query_scale(jnp.broadcast_to(pos, (b, s)), cfg.rope_parameters)
        q = (q.astype(jnp.float32) * a_t[..., None, None]).astype(h.dtype)
        q_nope = q[..., :dn]
        q_rope = rope_interleaved(q[..., dn:], pos, self._inv)
        down = a @ p["kv_a_w"]
        c_kv = rms_norm(down[..., :rank], p["kv_a_norm"], eps)
        k_rope = rope_interleaved(down[..., None, rank:], pos, self._inv)
        rows = jnp.concatenate([c_kv, k_rope[:, :, 0]], axis=-1)
        w_ukv = p["kv_b_w"].reshape(rank, nh, dn + dv)

        def whole(attend):
            with jax.named_scope("mla/expand"):
                kv = jnp.einsum("bsc,chd->bshd", c_kv, w_ukv)
                k = jnp.concatenate(
                    [kv[..., :dn],
                     jnp.broadcast_to(k_rope, (b, s, nh, dr))], axis=-1)
                qq = jnp.concatenate([q_nope, q_rope], axis=-1)
            return attend(qq, k, kv[..., dn:])

        def stored(attend):
            with jax.named_scope("mla/absorb"):
                q_abs = jnp.concatenate(
                    [jnp.einsum("bshd,chd->bshc", q_nope, w_ukv[..., :dn]),
                     q_rope], axis=-1)
            u = attend(q_abs)
            with jax.named_scope("mla/absorb"):
                return jnp.einsum("bshc,chd->bshd", u, w_ukv[..., dn:])

        o, extra = latent_fn(rows, whole, stored)
        h = h + o.reshape(b, s, nh * dv) @ p["o_w"]
        flat = rms_norm(h, p["ffn_norm"], eps).reshape(b * s, hidden)
        routed, stats = held_experts_arrays(
            flat, p["router_w"], p["expert_bias"],
            (p["exp_gate_w"], p["exp_up_w"], p["exp_down_w"]),
            cfg.first_expert, cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor,
            valid=None if valid is None else jnp.repeat(valid, s),
            scope="mistral4", norm_eps=1e-20)
        with jax.named_scope("mistral4/shared_expert"):
            shared = swiglu(flat, p["shared_gate_w"], p["shared_up_w"],
                            p["shared_down_w"])
        f = (routed + shared.astype(jnp.float32)).astype(h.dtype)
        return h + f.reshape(b, s, hidden), extra, stats

    def logits(self, params, h):
        hn = rms_norm(h, params["final_norm"], self.cfg.rms_norm_eps)
        return jnp.einsum("bsh,vh->bsv", hn, params["head"])

    def last_logits(self, params, h):
        # the norm and the head are per position: take the row first
        return self.logits(params, h[:, -1:])[:, 0]


class Mistral4ForCausalLM(Layer):
    """The mistral4 decoder (this chip's share of it), a layer's weights
    at a time."""

    def __init__(self, cfg: Mistral4Config):
        super().__init__()
        self.cfg = cfg
        L, H = cfg.num_hidden_layers, cfg.hidden_size
        nh = cfg.num_attention_heads
        i_m = cfg.moe_intermediate_size
        i_s = i_m * cfg.n_shared_experts
        n, e = cfg.n_routed_experts, cfg.router_experts
        # name -> (how many layers hold one, its shape); 0: the model's own
        shapes = {
            "embed": (0, [cfg.vocab_size, H]),
            "head": (0, [cfg.vocab_size, H]), "final_norm": (0, [H]),
            "in_norm": (L, [H]), "ffn_norm": (L, [H]),
            "q_a_w": (L, [H, cfg.q_lora_rank]),
            "q_a_norm": (L, [cfg.q_lora_rank]),
            "q_b_w": (L, [cfg.q_lora_rank, nh * cfg.qk_head_dim]),
            "kv_a_w": (L, [H, cfg.kv_lora_rank + cfg.qk_rope_head_dim]),
            "kv_a_norm": (L, [cfg.kv_lora_rank]),
            "kv_b_w": (L, [cfg.kv_lora_rank,
                           nh * (cfg.qk_nope_head_dim + cfg.v_head_dim)]),
            "o_w": (L, [nh * cfg.v_head_dim, H]),
            "router_w": (L, [H, e]), "expert_bias": (L, [e]),
            "exp_gate_w": (L, [n, H, i_m]), "exp_up_w": (L, [n, H, i_m]),
            "exp_down_w": (L, [n, i_m, H]),
            "shared_gate_w": (L, [H, i_s]), "shared_up_w": (L, [H, i_s]),
            "shared_down_w": (L, [i_s, H]),
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

    def serving_form(self) -> Mistral4ServingForm:
        if self._form is None:
            self._form = Mistral4ServingForm(self)
        return self._form

    def forward_arrays(self, params, ids):
        """Array level: logits [B, S, V] of whole sequences `ids` under
        `params` (`param_arrays()`): the serving form's layers in the
        expanded form over flash attention, no cache.  Pass the weights as
        ARGUMENTS of a `jax.jit`, not closed over."""
        from ..ops.pallas_ops import flash_attention_arrays

        form = self.serving_form()
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)
        h = form.embed(params, ids, pos)
        for l, spec in enumerate(form.layer_specs):
            h, _, _ = form.layer(
                l, params, h, pos,
                lambda rows, whole, stored, spec=spec: (whole(
                    lambda q, k, v: flash_attention_arrays(
                        q, k, v, is_causal=True, scale=spec.scale)), None))
        return form.logits(params, h)

    def forward(self, input_ids):
        """Logits [B, S, V] of whole sequences.  Forward only."""
        tensors, tree = jax.tree_util.tree_flatten(
            self._weights, is_leaf=lambda x: not isinstance(x, (list, dict)))

        def fn(ids, *flat):
            return self.forward_arrays(
                jax.tree_util.tree_unflatten(tree, flat), ids)

        return apply(fn, input_ids, *tensors, name="mistral4_forward")
