"""Plain reference of the `lfm2_moe` decoder (LiquidAI LFM2-24B-A2B; the
published `config.json` and the LFM2 family's published modeling code):

  h = embed[ids]                               (no scaling)
  per layer l (pre-norm, no post-norms):
    h = h + op(rmsnorm(h, operator_norm[l]))
    h = h + ff(rmsnorm(h, ffn_norm[l]))
  logits = rmsnorm(h, embedding_norm) @ embed^T   (the head is tied)

  op, layer_types[l] == "conv" (gated short convolution, conv_L_cache taps
  K = 3, no bias):
    [B, C, x] = split(a @ W_in, 3);  u = B * x
    c[t] = sum_k w[:, k] * u[t - (K-1) + k]    (depthwise, causal, zeros
                                                before the sequence's start)
    y = (C * c) @ W_out
  op, "full_attention": q, k, v = a @ W_q, W_k, W_v (no biases); RMSNorm
    over each head of q and of k; rotary positions (rotate-half over the
    whole head, rope_theta) on q and k; causal softmax attention at scale
    head_dim^-0.5, query head h reading K/V head h // (Hq / Hkv); o @ W_o
  ff, l < num_dense_layers: W_down(silu(W_gate m) * W_up m)
  ff, after: s = sigmoid(m @ W_router) in float32; selection
    top_k(s + expert_bias); weights s[sel] / (sum(s[sel]) + 1e-6) times
    routed_scaling_factor; y = sum_k w_k * expert_k(m); no shared expert

RMSNorm: x * rsqrt(mean(x^2) + norm_eps) * w.

`jax.numpy` in float32 under `default_matmul_precision("highest")`; no
kernel, no cache, no state, no batching: one sequence at a time, one layer
after another, the convolution over the whole sequence by shifted adds,
attention by blocks of query rows, a Python loop over the experts, each
upcast when it is used, so that 4,608 positions fit beside bfloat16
weights of 10.4 GB.

Departures from the published model, all stated by the configuration
file: `head_dim` = hidden / heads, the tied head and the per-head norms of
q and k are the family's code, not keys of `config.json` (`assumed`);
`expert_bias` is a trained buffer and zero here; `held = range(first,
first + n)` names the experts this chip holds (the benchmark
configuration holds all 64 of 64, so nothing is left out there; a smaller
share leaves out what the absent experts would add, the router keeping its
width and the weights normalised over all top-k).  Dropout and the
training-time bias update are absent.

`params` (arrays in whatever type the system holds them; a per-layer
weight is a list, indexed over the layers of ITS kind):
  embed [V,H]  embedding_norm [H]
  over all L layers: operator_norm ffn_norm [H]
  over the convolution layers: conv_in_w [H,3H]  conv_w [H,K]  conv_out_w [H,H]
  over the attention layers: q_w [H,Hq*D]  k_w v_w [H,Hkv*D]  o_w [Hq*D,H]
    q_norm k_norm [D]
  over the dense layers: dense_gate_w dense_up_w [H,I]  dense_down_w [I,H]
  over the expert layers: router_w [H,E]  expert_bias [E]
    exp_gate_w exp_up_w [n,H,Im]  exp_down_w [n,Im,H]
`cfg` is the configuration file's content (a dict): the sizes under the
published names, `layer_types`, and `harness.kwargs.router_experts` /
`first_expert`.

`fault` computes a WRONG reference on purpose, to show what a limit on the
comparison catches (PERF.md gives the readings): "fp8" rounds every matrix
product's operands to float8_e4m3 (the precision below the bfloat16 the
configuration states), "no_conv_history" lets the convolution see only the
current position (a state that is never carried), "drop_expert" leaves
out each token's last selected expert, "no_qk_norm" skips the per-head
norms of q and k, and "bf16_routing" takes the router's scores in
bfloat16.
"""
import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256          # query rows per attention block
ROUTE_EPS = 1e-6       # under the routing weights' sum (the family's code)

PARAM_NAMES = (
    "embed", "embedding_norm", "operator_norm", "ffn_norm", "conv_in_w",
    "conv_w", "conv_out_w", "q_w", "k_w", "v_w", "o_w", "q_norm", "k_norm",
    "dense_gate_w", "dense_up_w", "dense_down_w", "router_w", "expert_bias",
    "exp_gate_w", "exp_up_w", "exp_down_w")

FAULTS = (None, "fp8", "no_conv_history", "drop_expert", "no_qk_norm",
          "bf16_routing")


def params_from_model(model):
    """The arrays of an `Lfm2MoeForCausalLM`, by name."""
    held = model.param_arrays()
    return {n: held[n] for n in PARAM_NAMES}


def held_range(cfg):
    kw = cfg.get("harness", {}).get("kwargs", {})
    first = int(kw.get("first_expert", 0))
    return range(first, first + int(cfg["num_experts"]))


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


def _attention(q, k, v):
    """q [S,Hq,D], k v [S,Hkv,D] -> [S,Hq,D]; query head h reads K/V head
    h // (Hq / Hkv); key j is visible to query i iff j <= i.  Blocks of
    Q_BLOCK query rows."""
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
        scores = jnp.where((j[None, :] <= i[:, None])[None, None], scores,
                           -jnp.inf)
        # a padded query row past the end sees keys, so no row is all -inf
        att = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", att, v)

    out = jax.lax.map(block, (jnp.arange(qb.shape[0]) * Q_BLOCK, qb))
    return out.reshape(-1, hq, d)[:s]


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _conv_op(x, norm_w, p, *, eps, fault):
    """The gated short convolution over a whole sequence x [S, H], by
    shifted adds: tap k of K reads the input K - 1 - k positions back."""
    a = _rms(x, norm_w, eps)
    gate_in, gate_out, xx = jnp.split(_mm(a, p["conv_in_w"], fault), 3,
                                      axis=-1)
    u = gate_in * xx
    w = _f32(p["conv_w"])                                       # [H, K]
    taps = w.shape[1]
    c = w[:, taps - 1] * u
    if fault != "no_conv_history":
        for k in range(taps - 1):
            back = taps - 1 - k
            c = c + w[:, k] * jnp.pad(u, ((back, 0), (0, 0)))[:u.shape[0]]
    return x + _mm(gate_out * c, p["conv_out_w"], fault)


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "d", "eps",
                                             "theta", "fault"))
def _attn_op(x, norm_w, p, *, hq, hkv, d, eps, theta, fault):
    s = x.shape[0]
    a = _rms(x, norm_w, eps)
    q = _mm(a, p["q_w"], fault).reshape(s, hq, d)
    k = _mm(a, p["k_w"], fault).reshape(s, hkv, d)
    v = _mm(a, p["v_w"], fault).reshape(s, hkv, d)
    if fault != "no_qk_norm":
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    pos = jnp.arange(s)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    o = _attention(q, k, v).reshape(s, hq * d)
    return x + _mm(o, p["o_w"], fault)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ffn_in(x, w, *, eps):
    return _rms(x, w, eps)


@functools.partial(jax.jit, static_argnames=("fault",))
def _dense_mlp(m, p, *, fault):
    return _swiglu(m, p["dense_gate_w"], p["dense_up_w"], p["dense_down_w"],
                   fault)


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale",
                                             "fault"))
def _route(m, router_w, bias, *, top_k, route_scale, fault):
    """-> (sel [S,k], w [S,k]): float32 scores, top-k of score + bias,
    weights the selected scores over their sum + ROUTE_EPS, times
    route_scale."""
    if fault == "bf16_routing":
        scores = _f32(m.astype(jnp.bfloat16) @ router_w.astype(jnp.bfloat16))
    else:
        scores = _mm(m, router_w, fault)
    s = jax.nn.sigmoid(scores)
    _, sel = jax.lax.top_k(s + _f32(bias), top_k)
    picked = jnp.take_along_axis(s, sel, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + ROUTE_EPS) * route_scale
    if fault == "drop_expert":
        w = w.at[:, -1].set(0.0)
    return sel, w


@functools.partial(jax.jit, static_argnames=("fault",))
def _one_expert(m, wg, wu, wd, weight, *, fault):
    return _swiglu(m, wg, wu, wd, fault) * weight[:, None]


def _expert_mlp(m, p, held, first_held, top_k, route_scale, fault):
    sel, w = _route(m, p["router_w"], p["expert_bias"], top_k=top_k,
                    route_scale=route_scale, fault=fault)
    f = jnp.zeros_like(m)
    for e in held:                      # absent experts add nothing
        weight = jnp.where(sel == e, w, 0.0).sum(-1)
        i = e - first_held
        f = f + _one_expert(m, p["exp_gate_w"][i], p["exp_up_w"][i],
                            p["exp_down_w"][i], weight, fault=fault)
    return f


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(x, norm_w, embed, *, eps, fault):
    return _mm(_rms(x, norm_w, eps), embed.T, fault)


def logits(params, ids, cfg, held=None, fault=None, layer_out=None):
    """Float32 logits [S, V] of one sequence `ids` [S].  `held` defaults
    to the configuration's own share.  `layer_out`, a list, receives each
    layer's feed-forward term `f` for the tests.  `fault`: see the module
    docstring."""
    assert fault in FAULTS, fault
    held = held_range(cfg) if held is None else held
    eps = float(cfg["norm_eps"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg.get("head_dim") or cfg["hidden_size"] // hq)
    n_dense = int(cfg["num_dense_layers"])
    kinds = list(cfg["layer_types"])
    # the stacked expert weights hold `first_expert ..` in order
    first_held = held_range(cfg)[0]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][ids])
        for l, kind in enumerate(kinds):
            i = kinds[:l].count(kind)     # layer l's index within its kind
            if kind == "conv":
                x = _conv_op(x, params["operator_norm"][l],
                             {n: params[n][i] for n in (
                                 "conv_in_w", "conv_w", "conv_out_w")},
                             eps=eps, fault=fault)
            else:
                x = _attn_op(
                    x, params["operator_norm"][l],
                    {n: params[n][i] for n in (
                        "q_w", "k_w", "v_w", "o_w", "q_norm", "k_norm")},
                    hq=hq, hkv=hkv, d=d, eps=eps,
                    theta=float(cfg["rope_parameters"]["rope_theta"]),
                    fault=fault)
            m = _ffn_in(x, params["ffn_norm"][l], eps=eps)
            if l < n_dense:
                f = _dense_mlp(m, {n: params[n][l] for n in (
                    "dense_gate_w", "dense_up_w", "dense_down_w")},
                    fault=fault)
            else:
                f = _expert_mlp(
                    m, {n: params[n][l - n_dense] for n in (
                        "router_w", "expert_bias", "exp_gate_w", "exp_up_w",
                        "exp_down_w")},
                    held, first_held, int(cfg["num_experts_per_tok"]),
                    float(cfg["routed_scaling_factor"]), fault)
            if layer_out is not None:
                layer_out.append(f)
            x = x + f
        return _head(x, params["embedding_norm"], params["embed"], eps=eps,
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
