"""Plain reference of the `mistral4` decoder (Mistral-Small-4-119B-2603,
the language model on text; the published `config.json`, whose key set is
the DeepSeek-V3 family's, and that family's published modeling code):

  x = embed[ids]
  per layer (pre-norm, no post-norms), H heads i:
    h = rmsnorm(x, in_norm)
    c_q = rmsnorm(h @ W_dq, q_a_norm);  q_i = (c_q @ W_uq)_i = [q_i^nope | q_i^rope]
    [c_kv | k^rope] = h @ W_dkv;  c_kv <- rmsnorm(c_kv, kv_a_norm)
    [k_i^nope | v_i] = (c_kv @ W_ukv)_i
    q_i^rope, k^rope <- rotary positions over INTERLEAVED pairs (2j, 2j+1)
      at YaRN's frequencies; k^rope is shared by the heads
    s_ij = a_t * scale * (q_i^nope . k_ij^nope + q_i^rope . k_j^rope), j <= t
      scale = qk_head_dim^-0.5 * (0.1 * mscale_all_dim * ln(factor) + 1)^2
      a_t = 1 + llama_4_scaling_beta * ln(1 + floor(t / original_max_position_embeddings))
    x = x + concat_i(sum_j softmax_j(s_ij) v_ij) @ W_o
    m = rmsnorm(x, ffn_norm)
    r = sigmoid(m @ W_router) in float32; selection top_k(r + expert_bias);
      weights r[sel] / sum(r[sel]) * routed_scaling_factor
    x = x + sum_k w_k * expert_k(m) + shared_expert(m)
      every expert W_down(silu(W_gate m) * W_up m)
  logits = rmsnorm(x, final_norm) @ head^T

RMSNorm: x * rsqrt(mean(x^2) + rms_norm_eps) * w.

YaRN (`rope_parameters`): pair j of D/2 turns f_j = theta^(-2j/D) a
position; with c(n) = D * ln(original / (2 pi n)) / (2 ln theta), low =
floor(c(beta_fast)), high = ceil(c(beta_slow)), ramp_j = clip((j - low) /
(high - low), 0, 1): the frequency is f_j * (1 - ramp_j) + f_j / factor *
ramp_j; cos and sin are not scaled (mscale == mscale_all_dim).

`jax.numpy` in float32 under `default_matmul_precision("highest")`, the
EXPANDED form only: per-head keys and values from the latent, no
absorption, no cache, no kernel, no batching: one sequence at a time, one
layer after another, attention by blocks of query rows, a Python loop over
the experts, each upcast when it is used, so that 17,408 positions fit
beside bfloat16 weights of 7.6 GB.

Departures from the published model, all stated by the configuration
file: the router's score function, the selection bias (a trained buffer,
zero here), `scale`, `a_t` and the initial weights are not keys of
`config.json` (`assumed`); the vision tower is absent; `held = range(first,
first + n)` names the experts this chip holds, and what the absent experts
would add is left out, the router keeping its width and the weights
normalised over all top-k.

`params` (arrays in whatever type the system holds them; a per-layer
weight is a list over the layers):
  embed head [V,H]  final_norm [H]
  in_norm ffn_norm [H]  q_a_w [H,Rq]  q_a_norm [Rq]  q_b_w [Rq,Hq*(Dn+Dr)]
  kv_a_w [H,Rkv+Dr]  kv_a_norm [Rkv]  kv_b_w [Rkv,Hq*(Dn+Dv)]  o_w [Hq*Dv,H]
  router_w [H,E]  expert_bias [E]  exp_gate_w exp_up_w [n,H,Im]
  exp_down_w [n,Im,H]  shared_gate_w shared_up_w [H,Is]  shared_down_w [Is,H]
`cfg` is the configuration file's content (a dict): the sizes under the
published names and `harness.kwargs.router_experts` / `first_expert`.

Ties.  `top_k` is a step function of real numbers, and a program in
bfloat16 cannot tell a 4th score from a 5th that agree to bfloat16's step:
it sends about one position in forty of a routed layer to another expert
than float32 does, both being the model's output to the stated precision
(PERF.md section 6, PR 34: with the selections agreed the program IS this
reference to 0.05 rms of a logit; with them apart its worst token of a
check reads 1.2-3.4 logits, since the first routed layer acts on the bare
embedding and ONE expert is a quarter of what it writes).  So
`greedy_margins` measures a served token against the nearer of the
routings that precision cannot tell apart: wherever the last selected
score and the first left out differ by at most TIE of the former, and one
of the two experts is held here (two absent ones exchanged move the held
experts' weights by less than TIE), the position is ALSO carried on with the two exchanged, a BRANCH: its own
residual, query and cache row from there on, read against the main path's
keys and values at the positions before it, routed like any row in the
layers after, and free to branch again.  A layer starts at most
len(ids) / SPAWN branches, the closest ties first.  A position's margin is
the least over its branches; `logits` is the main path alone, and with
TIE = 0 `greedy_margins` is the main path's margins too.

`fault` computes a WRONG reference on purpose, to show what a limit on the
comparison catches (PERF.md gives the readings): "fp8" rounds every matrix
product's operands to float8_e4m3 (the precision below the bfloat16 the
configuration states), "drop_expert" leaves out each token's last selected
expert, "no_rope_key" zeroes k^rope (a cache row without its positional
part), "no_query_scale" takes a_t = 1, "yarn_off" the plain frequencies
f_j.
"""
import functools
import math

import jax
import jax.numpy as jnp

Q_BLOCK = 256          # query rows per attention block
TIE = 2.0 ** -8        # bfloat16's step: scores this close are a tie
SPAWN = 16             # a layer branches at most len(ids) / SPAWN positions

PARAM_NAMES = (
    "embed", "head", "final_norm", "in_norm", "ffn_norm", "q_a_w",
    "q_a_norm", "q_b_w", "kv_a_w", "kv_a_norm", "kv_b_w", "o_w",
    "router_w", "expert_bias", "exp_gate_w", "exp_up_w", "exp_down_w",
    "shared_gate_w", "shared_up_w", "shared_down_w")

FAULTS = (None, "fp8", "drop_expert", "no_rope_key", "no_query_scale",
          "yarn_off")


def params_from_model(model):
    """The arrays of a `Mistral4ForCausalLM`, by name."""
    held = model.param_arrays()
    return {n: held[n] for n in PARAM_NAMES}


def held_range(cfg):
    kw = cfg.get("harness", {}).get("kwargs", {})
    first = int(kw.get("first_expert", 0))
    return range(first, first + int(cfg["n_routed_experts"]))


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


def yarn_frequencies(dim, rp, fault=None):
    """[dim / 2] float32: the module docstring's rule."""
    theta, factor = float(rp["rope_theta"]), float(rp["factor"])
    original = float(rp["original_max_position_embeddings"])
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * j / dim)
    if fault == "yarn_off":
        return plain

    def c(n):
        return dim * math.log(original / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(c(float(rp["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rp["beta_slow"]))), dim - 1)
    ramp = jnp.clip((j - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / factor * ramp


def softmax_scale(cfg):
    rp = cfg["rope_parameters"]
    m = 0.1 * float(rp["mscale_all_dim"]) * math.log(float(rp["factor"])) + 1
    return float(cfg["qk_head_dim"]) ** -0.5 * m * m


def position_scale(pos, rp, fault=None):
    """a_t [S] float32."""
    if fault == "no_query_scale":
        return jnp.ones(pos.shape, jnp.float32)
    whole = pos // int(rp["original_max_position_embeddings"])
    return 1.0 + float(rp["llama_4_scaling_beta"]) * jnp.log(
        1.0 + _f32(whole))


def _rope_pairs(x, pos, freq):
    """Rotate the interleaved pairs (x[2j], x[2j+1]) of x [S, heads, D] in
    place by pos * freq_j."""
    s, h, d = x.shape
    ang = _f32(pos)[:, None, None] * freq[None, None, :]       # [S,1,D/2]
    pairs = x.reshape(s, h, d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(s, h, d)


def _swiglu(x, wg, wu, wd, fault=None):
    return _mm(jax.nn.silu(_mm(x, wg, fault)) * _mm(x, wu, fault), wd,
               fault)


def _attention(q, pos, a_t, k, v, own_k, own_v, scale):
    """Rows q [N,H,Dq] at positions pos [N], a_t [N], each against the
    sequence's keys k [S,H,Dq] and values v [S,H,Dv] at the positions
    BEFORE its own and its own key and value own_k, own_v [N,H,.] (the
    main path's rows are the sequence's own) -> [N,H,Dv].  Blocks of
    Q_BLOCK rows."""
    n, h, d = q.shape
    pad = -n % Q_BLOCK

    def blocks(x, fill=0):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1),
                    constant_values=fill)
        return x.reshape((-1, Q_BLOCK) + x.shape[1:])

    j = jnp.arange(k.shape[0])

    def block(args):
        qq, pp, aa, ok, ov = args
        aa = (scale * aa)[None, :, None]
        past = jnp.einsum("qhd,khd->hqk", qq, k) * aa
        past = jnp.where((j[None, :] < pp[:, None])[None], past, -jnp.inf)
        own = jnp.einsum("qhd,qhd->hq", qq, ok)[..., None] * aa
        att = jax.nn.softmax(jnp.concatenate([past, own], -1), axis=-1)
        return (jnp.einsum("hqk,khd->qhd", att[..., :-1], v)
                + att[..., -1].T[..., None] * ov)

    out = jax.lax.map(block, (blocks(q), blocks(pos), blocks(a_t, 1.0),
                              blocks(own_k), blocks(own_v)))
    return out.reshape(-1, h, v.shape[-1])[:n]


@functools.partial(jax.jit, static_argnames=(
    "heads", "dn", "dr", "dv", "rank", "eps", "fault", "rope"))
def _qkv(x, pos, p, *, heads, dn, dr, dv, rank, eps, fault, rope):
    """Rows x [N,Hd] at positions pos -> (q [N,H,Dn+Dr], k the same,
    v [N,H,Dv], a_t [N]): per-head queries, keys and values, rotated."""
    rp = dict(rope)
    s = x.shape[0]
    a = _rms(x, p["in_norm"], eps)
    c_q = _rms(_mm(a, p["q_a_w"], fault), p["q_a_norm"], eps)
    q = _mm(c_q, p["q_b_w"], fault).reshape(s, heads, dn + dr)
    down = _mm(a, p["kv_a_w"], fault)
    c_kv = _rms(down[:, :rank], p["kv_a_norm"], eps)
    kv = _mm(c_kv, p["kv_b_w"], fault).reshape(s, heads, dn + dv)
    freq = yarn_frequencies(dr, rp, fault)
    q_rope = _rope_pairs(q[..., dn:], pos, freq)
    k_rope = _rope_pairs(down[:, None, rank:], pos, freq)
    if fault == "no_rope_key":
        k_rope = jnp.zeros_like(k_rope)
    qq = jnp.concatenate([q[..., :dn], q_rope], axis=-1)
    kk = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_rope, (s, heads, dr))], axis=-1)
    return qq, kk, kv[..., dn:], position_scale(pos, rp, fault)


@functools.partial(jax.jit, static_argnames=("scale", "fault"))
def _attend(x, pos, qkv, k, v, o_w, *, scale, fault):
    """x + W_o(attention of the rows `qkv` over the sequence's k, v)."""
    q, own_k, own_v, a_t = qkv
    o = _attention(q, pos, a_t, k, v, own_k, own_v, scale)
    return x + _mm(o.reshape(x.shape[0], -1), o_w, fault)


@functools.partial(jax.jit, static_argnames=("eps",))
def _ffn_in(x, w, *, eps):
    return _rms(x, w, eps)


@functools.partial(jax.jit, static_argnames=("top_k", "route_scale",
                                             "fault", "held"))
def _route(m, router_w, bias, *, top_k, route_scale, fault, held):
    """-> (sel [S,k], w [S,k], other sel, other w, gap [S]): float32
    sigmoid scores, top-k of score + bias, weights the selected scores
    over their sum, times route_scale; the OTHER selection takes the first
    score left out in place of the last selected, and `gap` is how far
    apart those two lie, as a share of the larger (inf where neither
    expert is in `held`: exchanging them adds and removes nothing here)."""
    r = jax.nn.sigmoid(_mm(m, router_w, fault))
    top, first = jax.lax.top_k(r + _f32(bias), top_k + 1)

    def weights(sel):
        picked = jnp.take_along_axis(r, sel, axis=-1)
        w = picked / picked.sum(-1, keepdims=True) * route_scale
        return w.at[:, -1].set(0.0) if fault == "drop_expert" else w

    sel = first[:, :top_k]
    other = jnp.concatenate([first[:, :top_k - 1], first[:, top_k:]], -1)
    pair = first[:, top_k - 1:]
    here = ((pair >= held[0]) & (pair < held[1])).any(-1)
    gap = (top[:, top_k - 1] - top[:, top_k]) / jnp.abs(top[:, top_k - 1])
    return (sel, weights(sel), other, weights(other),
            jnp.where(here, gap, jnp.inf))


@functools.partial(jax.jit, static_argnames=("fault",))
def _one_expert(m, wg, wu, wd, weight, *, fault):
    return _swiglu(m, wg, wu, wd, fault) * weight[:, None]


@functools.partial(jax.jit, static_argnames=("fault",))
def _shared(m, wg, wu, wd, *, fault):
    return _swiglu(m, wg, wu, wd, fault)


def _experts(m, sel, w, p, held, first_held, fault, shared=True):
    """sum_k w_k expert_{sel_k}(m) over the experts in `held` (+ the
    shared expert)."""
    f = jnp.zeros_like(m)
    for e in held:                      # absent experts add nothing
        weight = jnp.where(sel == e, w, 0.0).sum(-1)
        i = e - first_held
        f = f + _one_expert(m, p["exp_gate_w"][i], p["exp_up_w"][i],
                            p["exp_down_w"][i], weight, fault=fault)
    if shared:
        f = f + _shared(m, p["shared_gate_w"], p["shared_up_w"],
                        p["shared_down_w"], fault=fault)
    return f


def _expert_mlp(m, p, held, first_held, top_k, route_scale, fault,
                shared=True, ties=None):
    """The feed-forward term of rows m.  `ties`, a list, receives (the
    other selection, its weights, the gap) of `_route`."""
    sel, w, *other = _route(
        m, p["router_w"], p["expert_bias"], top_k=top_k,
        route_scale=route_scale, fault=fault,
        held=(held[0], held[-1] + 1) if len(held) else (0, 0))
    if ties is not None:
        ties.append(other)
    return _experts(m, sel, w, p, held, first_held, fault, shared)


@functools.partial(jax.jit, static_argnames=("n", "tie"))
def _closest(groups, n, tie):
    """groups: (gap [N], alive [N] or None, *columns [N,..]) of the main
    path and of each branch -> (*columns, alive) of the n rows with the
    smallest gaps; alive where the gap is a tie.  One program a layer:
    the eager pieces would compile one by one."""
    gaps = jnp.concatenate([g if a is None else jnp.where(a, g, jnp.inf)
                            for g, a, *_ in groups])
    least, at = jax.lax.top_k(-gaps, n)
    return [jnp.concatenate(col)[at]
            for col in list(zip(*groups))[2:]] + [-least <= tie]


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head(x, norm_w, head, *, eps, fault):
    return _mm(_rms(x, norm_w, eps), head.T, fault)


_ATTN = ("in_norm", "q_a_w", "q_a_norm", "q_b_w", "kv_a_w", "kv_a_norm",
         "kv_b_w", "o_w")
_MOE = ("router_w", "expert_bias", "exp_gate_w", "exp_up_w", "exp_down_w",
        "shared_gate_w", "shared_up_w", "shared_down_w")


def _forward(params, ids, cfg, held, fault, layer_out, shared, tie):
    """-> (logits [S,V] of the main path, branches): a branch is (pos [n],
    logits [n,V], alive [n]); none where `tie`, the gap that counts as a
    tie, is 0."""
    assert fault in FAULTS, fault
    held = held_range(cfg) if held is None else held
    eps = float(cfg["rms_norm_eps"])
    # the stacked expert weights hold `first_expert ..` in order
    first_held = held_range(cfg)[0]
    rope = tuple(sorted((k, v) for k, v in cfg["rope_parameters"].items()
                        if not isinstance(v, str)))
    shape = dict(heads=int(cfg["num_attention_heads"]),
                 dn=int(cfg["qk_nope_head_dim"]),
                 dr=int(cfg["qk_rope_head_dim"]), dv=int(cfg["v_head_dim"]),
                 rank=int(cfg["kv_lora_rank"]), eps=eps, fault=fault,
                 rope=rope)
    top_k = int(cfg["num_experts_per_tok"])
    room = max(len(ids) // SPAWN, 1) if tie else 0   # branches a layer
    with jax.default_matmul_precision("highest"):
        # rows[0] is the main path, the others branches: [pos, x, alive]
        rows = [[jnp.arange(len(ids)), _f32(params["embed"][ids]), None]]
        for l in range(int(cfg["num_hidden_layers"])):
            pa = {n: params[n][l] for n in _ATTN}
            pm = {n: params[n][l] for n in _MOE}
            k = v = None
            for r in rows:              # the main path first: its k, v
                qkv = _qkv(r[1], r[0], pa, **shape)
                if k is None:
                    k, v = qkv[1], qkv[2]
                r[1] = _attend(r[1], r[0], qkv, k, v, pa["o_w"],
                               scale=softmax_scale(cfg), fault=fault)
            seen, ms = [], []
            for r in rows:
                ms.append(_ffn_in(r[1], params["ffn_norm"][l], eps=eps))
                f = _expert_mlp(
                    ms[-1], pm, held, first_held, top_k,
                    float(cfg["routed_scaling_factor"]), fault, shared,
                    seen if room else None)
                if layer_out is not None and r is rows[0]:
                    layer_out.append(f)
                r.append(r[1] + f)
            if room:                    # the closest ties, the other way
                pos, x, m, sel, w, alive = _closest(
                    tuple((gap, r[2], r[0], r[1], m_, sel_, w_)
                          for r, m_, (sel_, w_, gap) in zip(rows, ms, seen)),
                    room, tie)
                born = [pos, x + _experts(m, sel, w, pm, held, first_held,
                                          fault, shared), alive]
            rows = [[r[0], r[3], r[2]] for r in rows]
            if room:
                rows.append(born)
        lg = [_head(r[1], params["final_norm"], params["head"], eps=eps,
                    fault=fault) for r in rows]
        return lg[0], [(r[0], g, r[2]) for r, g in zip(rows[1:], lg[1:])]


def logits(params, ids, cfg, held=None, fault=None, layer_out=None,
           shared=True):
    """Float32 logits [S, V] of one sequence `ids` [S].  `held` defaults
    to the configuration's own share; `shared=False` leaves the shared
    expert out (a share that counts it elsewhere).  `layer_out`, a list,
    receives each layer's feed-forward term `f` for the tests.  `fault`:
    see the module docstring."""
    return _forward(params, ids, cfg, held, fault, layer_out, shared,
                    0.0)[0]


@jax.jit
def _margins(lg, ids):
    lg = lg[:-1]
    chosen = jnp.take_along_axis(lg, ids[1:, None], axis=-1)[:, 0]
    return lg.max(-1) - chosen, lg.std(-1)


@jax.jit
def _under(lg, chosen):
    return lg.max(-1) - jnp.take_along_axis(lg, chosen[:, None], -1)[:, 0]


def choice_margins(params, ids, chosen, cfg, fault=None, tie=None):
    """One sequence `ids` [S]: how far the reference logit of the token
    `chosen[p]` lies below position p's largest - the least over the
    position's branches (module docstring, Ties; `tie` defaults to TIE, 0
    reads the main path alone) - and the standard deviation of the main
    path's logits there.  -> (margins [S], spread [S]) float32."""
    ids, chosen = jnp.asarray(ids), jnp.asarray(chosen)
    lg, branches = _forward(params, ids, cfg, None, fault, None, True,
                            TIE if tie is None else tie)
    margins = _under(lg, chosen)
    for pos, blg, alive in branches:
        margins = margins.at[pos].min(
            jnp.where(alive, _under(blg, chosen[pos]), jnp.inf))
    return margins, lg.std(-1)


def greedy_margins(params, ids, cfg, **kw):
    """For rows of token ids [N,S]: at each position p < S-1, how far the
    reference logit of the token that actually follows lies below that
    position's largest (`choice_margins` of ids[p + 1]), and the standard
    deviation of that position's logits.  Two [N,S-1] float32 numpy
    arrays; one row at a time."""
    import numpy as np

    out = [choice_margins(params, row, np.roll(row, -1), cfg, **kw)
           for row in ids]
    return (np.stack([np.asarray(m)[:-1] for m, _ in out]),
            np.stack([np.asarray(s)[:-1] for _, s in out]))
