"""Plain reference of the `brumby` decoder (Manifest AI Brumby-14B-Base:
the published `config.json`, and for what it does not carry the family's
public description, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239, as the configuration file's `assumed` states it):

  h = embed[ids]
  per layer l:
    a      = rmsnorm(h, in_norm[l])
    q,k,v  = a Wq [S,Hq,D],  a Wk [S,Hkv,D],  a Wv [S,Hkv,D]    (no bias)
    log g  = logsigmoid(a Wg) [S,Hkv]                one gate a K/V head
    q, k   = rmsnorm over each head's D lanes (weights [D]), then rotary
             positions (rotate-half over the whole head, rope_theta)
    b_t    = sum_{r<=t} log g_r
    w_ts   = exp(b_t - b_s) * (q_t . k_s)^2 ,  s <= t       (degree 2)
    o_t    = sum_s w_ts v_s / sum_s w_ts        query head h reads K/V
                                                head h // (Hq / Hkv)
    h      = h + concat_h(o) Wo
    h      = h + (silu(n Wgate) * (n Wup)) Wdown,  n = rmsnorm(h, post_norm[l])
  logits = rmsnorm(h, final_norm) @ head^T                (the head is untied)

RMSNorm: x * rsqrt(mean(x^2) + rms_norm_eps) * w.

This is the ATTENTION form: every weight `w_ts` of a query is made and
summed, by blocks of query rows; there is no state, no chunk, no cache, no
feature map, no kernel and no batching here - one sequence at a time, one
layer after another, each layer's weights upcast when it is used and the
head a block of vocabulary rows at a time, so that 10,240 positions fit
beside bfloat16 weights of 7.1 GB.  `jax.numpy` in float32 under
`default_matmul_precision("highest")`.  The running sum `b` is taken on
the host in float64 and handed over as two float32 parts, so that `b_t -
b_s` is exact where it matters (near 0) at any length.  A denominator that
is exactly zero gives 0 (the program says the same).  Nothing is imported
from the program under test.

`params` (arrays in whatever type the system holds them; a per-layer
weight is a list over the layers):
  embed head [V,H]  final_norm [H]
  in_norm post_norm [H]  q_w [H,Hq*D]  k_w v_w [H,Hkv*D]  g_w [H,Hkv]
  o_w [Hq*D,H]  q_norm k_norm [D]  mlp_gate_w mlp_up_w [H,I]
  mlp_down_w [I,H]
`cfg` is the configuration file's content (a dict).

`fault` computes a WRONG reference on purpose, to show what a limit on the
comparison catches (PERF.md gives the readings): "float8" rounds every
matrix product's operands to float8_e4m3 (the precision below the
bfloat16 the configuration states), "no_gate" takes every gate as 1 (a
state that never forgets), "degree_1" weighs a key by `q . k` and not its
square, "no_carry" lets a query see only the keys of its own block of
1,024 positions (a state that is not carried from one chunk to the next),
"unnormalised" leaves out the division by the weights' sum.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256          # query rows per block of weights
P_BLOCK = 512          # positions per block of logits
V_BLOCKS = 16          # the head, upcast this many row blocks at a time
NO_CARRY = 1024        # fault "no_carry": positions a query's block spans

PARAM_NAMES = (
    "embed", "head", "final_norm", "in_norm", "post_norm", "q_w", "k_w",
    "v_w", "g_w", "o_w", "q_norm", "k_norm", "mlp_gate_w", "mlp_up_w",
    "mlp_down_w")
_LAYER = PARAM_NAMES[3:]
FAULTS = (None, "float8", "no_gate", "degree_1", "no_carry", "unnormalised")


def params_from_model(model):
    """The arrays of a `BrumbyForCausalLM`, by name."""
    held = model.param_arrays()
    return {n: held[n] for n in PARAM_NAMES}


def _f32(x):
    return x.astype(jnp.float32)


def _mm(x, w, fault):
    """x @ w in float32; under fault "float8" from operands rounded to
    it."""
    w = _f32(w)
    if fault == "float8":
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


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "d", "eps",
                                             "theta", "fault"))
def _qkvg(x, p, *, hq, hkv, d, eps, theta, fault):
    """-> q [S,Hq,D], k v [S,Hkv,D], log g [S,Hkv] of one layer."""
    s = x.shape[0]
    a = _rms(x, p["in_norm"], eps)
    pos = jnp.arange(s)
    q = _rms(_mm(a, p["q_w"], fault).reshape(s, hq, d), p["q_norm"], eps)
    k = _rms(_mm(a, p["k_w"], fault).reshape(s, hkv, d), p["k_norm"], eps)
    v = _mm(a, p["v_w"], fault).reshape(s, hkv, d)
    log_g = jax.nn.log_sigmoid(_mm(a, p["g_w"], fault))
    if fault == "no_gate":
        log_g = jnp.zeros_like(log_g)
    return _rope(q, pos, theta), _rope(k, pos, theta), v, log_g


@functools.partial(jax.jit, static_argnames=("fault",))
def _retention(q, k, v, b_hi, b_lo, *, fault):
    """The weights of every query over every key it sees, by blocks of
    Q_BLOCK query rows.  q [S,Hq,D], k v [S,Hkv,D]; b_hi + b_lo [S,Hkv]
    the gates' running sum.  -> [S,Hq,D]."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    pad = -s % Q_BLOCK

    def blocks(x):
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return x.reshape((-1, Q_BLOCK) + x.shape[1:])

    qb = blocks(q).reshape(-1, Q_BLOCK, hkv, hq // hkv, d)
    j = jnp.arange(s)

    def block(args):
        i0, qq, hi, lo = args
        i = i0 + jnp.arange(Q_BLOCK)
        dots = jnp.einsum("qhgd,khd->hgqk", qq, k)
        power = dots if fault == "degree_1" else dots * dots
        seen = j[None, :] <= i[:, None]
        if fault == "no_carry":
            seen &= j[None, :] >= (i[:, None] // NO_CARRY) * NO_CARRY
        # the parts apart: hi - hi is exact wherever the weight is not 0
        delta = (hi[:, None] - b_hi[None]) + (lo[:, None] - b_lo[None])
        decay = jnp.exp(jnp.minimum(delta, 0.0)).transpose(2, 0, 1)
        w = jnp.where(seen[None, None], decay[:, None] * power, 0.0)
        num = jnp.einsum("hgqk,khd->qhgd", w, v)
        if fault == "unnormalised":
            return num
        den = w.sum(-1).transpose(2, 0, 1)[..., None]
        return num / jnp.where(den == 0, 1.0, den)

    out = jax.lax.map(block, (jnp.arange(qb.shape[0]) * Q_BLOCK, qb,
                              blocks(b_hi), blocks(b_lo)))
    return out.reshape(-1, hq, d)[:s]


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _out_and_mlp(x, o, p, *, eps, fault):
    h = x + _mm(o.reshape(o.shape[0], -1), p["o_w"], fault)
    n = _rms(h, p["post_norm"], eps)
    f = _mm(jax.nn.silu(_mm(n, p["mlp_gate_w"], fault))
            * _mm(n, p["mlp_up_w"], fault), p["mlp_down_w"], fault)
    return h + f


def hidden(params, ids, cfg, fault=None):
    """Float32 hidden states [S, H] of one sequence `ids` [S] after the
    last layer (before the final norm).  `fault`: the module docstring."""
    assert fault in FAULTS, fault
    eps = float(cfg["rms_norm_eps"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][ids])
        for l in range(int(cfg["num_hidden_layers"])):
            p = {n: params[n][l] for n in _LAYER}
            q, k, v, log_g = _qkvg(x, p, hq=hq, hkv=hkv, d=d, eps=eps,
                                   theta=float(cfg["rope_theta"]),
                                   fault=fault)
            b = np.cumsum(np.asarray(log_g, np.float64), axis=0)
            b_hi = b.astype(np.float32)
            o = _retention(q, k, v, jnp.asarray(b_hi),
                           jnp.asarray((b - b_hi).astype(np.float32)),
                           fault=fault)
            x = _out_and_mlp(x, o, p, eps=eps, fault=fault)
        return x


@functools.partial(jax.jit, static_argnames=("eps", "fault"))
def _head_block(x, norm_w, head, *, eps, fault):
    """Logits [P, V] of a block of positions, the head upcast a block of
    its rows at a time."""
    hn = _rms(x, norm_w, eps)
    edges = np.linspace(0, head.shape[0], V_BLOCKS + 1).astype(int)
    return jnp.concatenate(
        [_mm(hn, head[a:b].T, fault) for a, b in zip(edges, edges[1:])
         if b > a], axis=-1)


def logits(params, ids, cfg, fault=None):
    """Float32 logits [S, V] of one sequence: for sequences whose logits
    fit (the tests'); `greedy_margins` never holds them whole."""
    x = hidden(params, ids, cfg, fault)
    with jax.default_matmul_precision("highest"):
        return _head_block(x, params["final_norm"], params["head"],
                           eps=float(cfg["rms_norm_eps"]), fault=fault)


@jax.jit
def _margins(lg, nxt):
    chosen = jnp.take_along_axis(lg, nxt[:, None], axis=-1)[:, 0]
    return lg.max(-1) - chosen, lg.std(-1)


def greedy_margins(params, ids, cfg, fault=None):
    """For rows of token ids [N,S]: at each position p < S-1, how far the
    reference logit of the token that actually follows lies below that
    position's largest, and the standard deviation of that position's
    logits.  Two [N,S-1] float32 numpy arrays; one row at a time, its
    logits a block of P_BLOCK positions at a time."""
    eps = float(cfg["rms_norm_eps"])
    out_m, out_s = [], []
    for row in ids:
        row = jnp.asarray(row)
        x = hidden(params, row, cfg, fault)[:-1]
        nxt = row[1:]
        pad = -x.shape[0] % P_BLOCK
        x = jnp.pad(x, ((0, pad), (0, 0)))
        nxt = jnp.pad(nxt, (0, pad))
        ms, ss = [], []
        with jax.default_matmul_precision("highest"):
            for a in range(0, x.shape[0], P_BLOCK):
                m, s = _margins(
                    _head_block(x[a:a + P_BLOCK], params["final_norm"],
                                params["head"], eps=eps, fault=fault),
                    nxt[a:a + P_BLOCK])
                ms.append(np.asarray(m))
                ss.append(np.asarray(s))
        n = len(row) - 1
        out_m.append(np.concatenate(ms)[:n])
        out_s.append(np.concatenate(ss)[:n])
    return np.stack(out_m), np.stack(out_s)
