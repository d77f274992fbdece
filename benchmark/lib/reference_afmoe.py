"""Plain reference of the `afmoe` decoder (Arcee Trinity; the Hugging Face
`modeling_afmoe.py` and the published `config.json`): token embedding
scaled by sqrt(hidden) (muP), RMSNorm before and after each half of a
block (sandwich), grouped-query attention with RMSNorm over each head of q
and k, rotary positions on the sliding-window layers ONLY (full layers see
no positions at all), a sigmoid gate on the attention output, a SwiGLU MLP
in the leading dense layers and, after them, a sigmoid-routed top-k
mixture of SwiGLU experts beside a shared expert, final RMSNorm and an
untied output head.

`jax.numpy` in float32 under `default_matmul_precision("highest")`; no
kernel, no cache, no batching: one sequence at a time, one layer after
another, a Python loop over the experts, each upcast when it is used, and
attention by blocks of query rows so that 8,448 positions fit beside
bfloat16 weights of 8.6 GB.

Departures from the published model, all stated by the configuration:
`held = range(first, first + n)` names the experts this chip holds; the
router keeps its published width, the weights are normalised over all
top-k selected experts (route_norm), and what an absent expert would add
is left out.  With `held = range(router width)` this is the uncut model.
`expert_bias` enters the selection only.  Dropout, the load-balance loss
and the bias update are training's and absent.

`params` (arrays in whatever type the system holds them):
  embed [V,H]  head [V,H]  final_norm [H]
  per layer l, stacked over the L layers (or a list of L arrays): in_norm post_attn_norm
  pre_mlp_norm post_mlp_norm [L,H]  q_w gate_w [L,H,Hq*D]  k_w v_w
  [L,H,Hkv*D]  o_w [L,Hq*D,H]  q_norm k_norm [L,D]
  stacked over the dense layers: dense_gate_w dense_up_w [Ld,H,I]
  dense_down_w [Ld,I,H]
  stacked over the expert layers: router_w [Lm,H,E]  expert_bias [Lm,E]
  exp_gate_w exp_up_w [Lm,n,H,Im]  exp_down_w [Lm,n,Im,H]
  shared_gate_w shared_up_w [Lm,H,Is]  shared_down_w [Lm,Is,H]
`cfg` is the configuration file's content (a dict): the sizes under the
published names, `layer_types`, and `harness.kwargs.router_experts` /
`first_expert`.

`fault` computes a WRONG reference on purpose, to show what a limit on
the comparison catches (PERF.md gives the readings): "fp8" rounds every
matrix product's operands to float8_e4m3 (the precision below the
bfloat16 the configuration states), "bf16_routing" takes the router's
scores in bfloat16, "no_window" lets sliding layers see every earlier
key, "drop_expert" leaves out each token's last selected expert, and
"wrong_block" reads keys and values 64..127 from positions 0..63.
"""
import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256          # query rows per attention block

PARAM_NAMES = (
    "embed", "head", "final_norm", "in_norm", "post_attn_norm",
    "pre_mlp_norm", "post_mlp_norm", "q_w", "k_w", "v_w", "gate_w", "o_w",
    "q_norm", "k_norm", "dense_gate_w", "dense_up_w", "dense_down_w",
    "router_w", "expert_bias", "exp_gate_w", "exp_up_w", "exp_down_w",
    "shared_gate_w", "shared_up_w", "shared_down_w")


def params_from_model(model):
    """The arrays of an `AfmoeForCausalLM`, by name; a per-layer weight is
    a list of arrays (indexed like a stacked one)."""
    held = model.param_arrays()
    return {n: held[n] for n in PARAM_NAMES}


def held_range(cfg):
    kw = cfg.get("harness", {}).get("kwargs", {})
    first = int(kw.get("first_expert", 0))
    return range(first, first + int(cfg["num_experts"]))


FAULTS = (None, "fp8", "bf16_routing", "no_window", "drop_expert",
          "wrong_block")


def _f32(x):
    return x.astype(jnp.float32)


def _mm(x, w, fault):
    """x @ w in float32; under fault "fp8" from operands rounded to it."""
    w = _f32(w)
    if fault == "fp8":
        x, w = (_f32(a.astype(jnp.float8_e4m3fn)) for a in (x, w))
    return x @ w


def _rms(x, w, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(w)


def _rope(x, pos, theta):
    """Rotate-half over the whole head: x [S, heads, D], pos [S]."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * inv[None]          # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(x, wg, wu, wd, fault=None):
    return _mm(jax.nn.silu(_mm(x, wg, fault)) * _mm(x, wu, fault), wd,
               fault)


def _attention(q, k, v, window):
    """q [S,Hq,D], k v [S,Hkv,D] -> [S,Hq,D]; query head h reads K/V head
    h // (Hq / Hkv); key j is visible to query i iff 0 <= i - j (< window
    on a sliding layer).  Blocks of Q_BLOCK query rows."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    pad = -s % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, hkv, hq // hkv, d)
    j = jnp.arange(s)

    def block(args):
        i0, qq = args
        i = i0 + jnp.arange(Q_BLOCK)
        scores = jnp.einsum("qhgd,khd->hgqk", qq, k) / math.sqrt(d)
        seen = j[None, :] <= i[:, None]
        if window is not None:
            seen &= i[:, None] - j[None, :] < window
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        # a padded query row past the end sees keys, so no row is all -inf
        att = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", att, v)

    out = jax.lax.map(block, (jnp.arange(qb.shape[0]) * Q_BLOCK, qb))
    return out.reshape(-1, hq, d)[:s]


@functools.partial(jax.jit, static_argnames=(
    "hq", "hkv", "d", "eps", "window", "theta", "fault"))
def _attn_half(x, p, *, hq, hkv, d, eps, window, theta, fault):
    s = x.shape[0]
    a = _rms(x, p["in_norm"], eps)
    q = _mm(a, p["q_w"], fault).reshape(s, hq, d)
    k = _mm(a, p["k_w"], fault).reshape(s, hkv, d)
    v = _mm(a, p["v_w"], fault).reshape(s, hkv, d)
    g = _mm(a, p["gate_w"], fault)
    q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    if window is not None:            # full layers: no positions at all
        pos = jnp.arange(s)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    if fault == "wrong_block" and s >= 128:
        k = k.at[64:128].set(k[:64])
        v = v.at[64:128].set(v[:64])
    seen = None if fault == "no_window" else window
    o = _attention(q, k, v, seen).reshape(s, hq * d) * jax.nn.sigmoid(g)
    x = x + _rms(_mm(o, p["o_w"], fault), p["post_attn_norm"], eps)
    return x, _rms(x, p["pre_mlp_norm"], eps)


@functools.partial(jax.jit, static_argnames=("fault",))
def _dense_mlp(m, p, *, fault):
    return _swiglu(m, p["dense_gate_w"], p["dense_up_w"], p["dense_down_w"],
                   fault)


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale",
                                             "fault"))
def _route(m, router_w, bias, *, top_k, route_scale, fault):
    """-> (sel [S,k], w [S,k]): float32 scores, top-k of score + bias,
    weights the selected scores over their sum, times route_scale."""
    if fault == "bf16_routing":
        scores = _f32(m.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16))
    else:
        scores = _mm(m, router_w, fault)
    s = jax.nn.sigmoid(scores)
    _, sel = jax.lax.top_k(s + _f32(bias), top_k)
    picked = jnp.take_along_axis(s, sel, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-20) * route_scale
    if fault == "drop_expert":
        w = w.at[:, -1].set(0.0)
    return sel, w


@functools.partial(jax.jit, static_argnames=("fault",))
def _one_expert(m, wg, wu, wd, weight, *, fault):
    return _swiglu(m, wg, wu, wd, fault) * weight[:, None]


def _expert_mlp(m, p, held, first_held, top_k, route_scale, fault):
    sel, w = _route(m, p["router_w"], p["expert_bias"], top_k=top_k,
                    route_scale=route_scale, fault=fault)
    f = _one_expert(m, p["shared_gate_w"], p["shared_up_w"],
                    p["shared_down_w"], jnp.ones(m.shape[0], jnp.float32),
                    fault=fault)
    for e in held:                      # absent experts add nothing
        weight = jnp.where(sel == e, w, 0.0).sum(-1)
        i = e - first_held
        f = f + _one_expert(m, p["exp_gate_w"][i], p["exp_up_w"][i],
                            p["exp_down_w"][i], weight, fault=fault)
    return f


@functools.partial(jax.jit, static_argnames=("eps",))
def _close(x, f, w, *, eps):
    return x + _rms(f, w, eps)


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(x, norm_w, head, *, eps, fault):
    return _mm(_rms(x, norm_w, eps), head.T, fault)


def logits(params, ids, cfg, held=None, fault=None, layer_out=None):
    """Float32 logits [S, V] of one sequence `ids` [S].  `held` defaults
    to the configuration's own share.  `layer_out`, a list, receives each
    layer's MLP contribution `f` (before its norm) for the tests.
    `fault`: see the module docstring."""
    assert fault in FAULTS, fault
    held = held_range(cfg) if held is None else held
    eps, hq = float(cfg["rms_norm_eps"]), int(cfg["num_attention_heads"])
    n_dense = int(cfg["num_dense_layers"])
    # the stacked expert weights hold `first_expert ..` in order
    first_held = held_range(cfg)[0]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][ids]) * math.sqrt(cfg["hidden_size"])
        for l, kind in enumerate(cfg["layer_types"]):
            p = {n: params[n][l] for n in (
                "in_norm", "post_attn_norm", "pre_mlp_norm", "q_w", "k_w",
                "v_w", "gate_w", "o_w", "q_norm", "k_norm")}
            x, m = _attn_half(
                x, p, hq=hq, hkv=int(cfg["num_key_value_heads"]),
                d=int(cfg["head_dim"]), eps=eps,
                window=(int(cfg["sliding_window"])
                        if kind == "sliding_attention" else None),
                theta=float(cfg["rope_theta"]), fault=fault)
            if l < n_dense:
                f = _dense_mlp(m, {n: params[n][l] for n in (
                    "dense_gate_w", "dense_up_w", "dense_down_w")},
                    fault=fault)
            else:
                f = _expert_mlp(
                    m, {n: params[n][l - n_dense] for n in (
                        "router_w", "expert_bias", "exp_gate_w", "exp_up_w",
                        "exp_down_w", "shared_gate_w", "shared_up_w",
                        "shared_down_w")},
                    held, first_held, int(cfg["num_experts_per_tok"]),
                    float(cfg["route_scale"]), fault)
            if layer_out is not None:
                layer_out.append(f)
            x = _close(x, f, params["post_mlp_norm"][l], eps=eps)
        return _head(x, params["final_norm"], params["head"], eps=eps,
                     fault=fault)


@jax.jit
def _margins(lg, ids):
    lg = lg[:-1]
    chosen = jnp.take_along_axis(lg, ids[1:, None], axis=-1)[:, 0]
    return lg.max(-1) - chosen, lg.std(-1)


def greedy_margins(params, ids, cfg, **kw):
    """For rows of token ids [N,S]: at each position p < S-1, how far the
    reference logit of the token that actually follows lies below that
    position's largest, and the standard deviation of that position's
    logits.  Two [N,S-1] float32 numpy arrays; one row at a time."""
    import numpy as np

    out = [_margins(logits(params, jnp.asarray(row), cfg, **kw),
                    jnp.asarray(row)) for row in ids]
    return (np.stack([np.asarray(m) for m, _ in out]),
            np.stack([np.asarray(s) for _, s in out]))
