"""Plain reference of the `falcon_h1` decoder (TII Falcon-H1: the published
`config.json`, whose keys name every multiplier, and the Mamba-2 mixer of
"Transformers are SSMs", arXiv:2405.21060, as the configuration file's
`assumed` states it):

  h = embed[ids] * embedding_multiplier
  per layer l:
    x      = rmsnorm(h, in_norm[l])
    attention:
      a       = x * attention_in_multiplier
      q, k, v = a Wq [S,Hq,D],  (a Wk) * key_multiplier [S,Hkv,D],  a Wv
      q, k    = rotary positions (rotate-half over the head, rope_theta)
      o       = causal softmax(q k^T / sqrt D) v      query head i reads
                                                      K/V head i // (Hq/Hkv)
      attn    = concat_h(o) Wo * attention_out_multiplier
    mixer:
      z | xBC | dt = ((x * ssm_in_multiplier) W_in) * mup   (mup: the five
                     parts z, x, B, C, dt times ssm_multipliers)
      xBC     = silu(sum_k conv_w[:, k] xBC[t-3+k] + conv_bias)  (zeros
                before the first position)
      xs | B | C = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
      per position t, head h, group g = h // (heads / groups), S [P, N]:
        S  = exp(dt_t[h] A[h]) S + dt_t[h] xs_t[h] B_t[g]^T
        y_t[h] = S C_t[g] + D[h] xs_t[h]
      ssm     = gnorm(y * silu(z)) W_out * ssm_out_multiplier   (RMS over
                each of the groups' equal parts, then times gate_norm)
    h      = h + attn + ssm
    h      = h + [silu((n Wg) * mlp_multipliers[0]) * (n Wu)] Wd
                 * mlp_multipliers[1],   n = rmsnorm(h, pre_ff_norm[l])
  logits = rmsnorm(h, final_norm) @ head^T * lm_head_multiplier  (untied)

RMSNorm: x * rsqrt(mean(x^2) + rms_norm_eps) * w.

The state-space part is the RECURRENCE, a position at a time over the
whole sequence (`jax.lax.scan`): no chunk, no kernel, no pool, no cache
and no batching - one sequence at a time, one layer after another, each
layer's weights upcast when it is used and the head a block of vocabulary
rows at a time.  Attention is made by blocks of query rows.  `jax.numpy`
in float32 under `default_matmul_precision("highest")`.  Nothing is
imported from the program under test: the weights are read from the model,
and `A_log`, `dt_bias` and `D`, which the model holds as fixed arrays and
the seed does not draw, are made here from the configuration's ranges
(`fixed_arrays`).

`params` (arrays in whatever type the system holds them; a per-layer
weight is a list over the layers):
  embed head [V,H]  final_norm [H]
  in_norm pre_ff_norm [H]  q_w [H,Hq*D]  k_w v_w [H,Hkv*D]  o_w [Hq*D,H]
  in_proj_w [H, 2*d_ssm + 2*G*N + heads]  conv_w [conv_dim, d_conv]
  conv_bias [conv_dim]  gate_norm [d_ssm]  out_proj_w [d_ssm,H]
  mlp_gate_w mlp_up_w [H,I]  mlp_down_w [I,H]
`cfg` is the configuration file's content (a dict).

`states` gives, a layer each, what a server keeps of a sequence after its
first `n` positions: the convolution's last `mamba_d_conv - 1` inputs and
the SSM state `[heads, mamba_d_head, mamba_d_state]`, under their group
names "conv" and "ssm".

`fault` computes a WRONG reference on purpose, to show what a limit on the
comparison catches (PERF.md gives the readings): "fp8" rounds every matrix
product's operands to float8_e4m3 (the precision below the bfloat16 the
configuration states), "bf16_state" rounds the state to bfloat16 after
every position, "no_ssm_carry" starts the state from zeros at every
`mamba_chunk_size` positions (a state not carried from one chunk to the
next), "no_conv_history" lets the convolution see a position's own input
alone (the inputs before it read as zeros), "no_attention" leaves the
attention branch out.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 256          # query rows per block of attention weights
P_BLOCK = 512          # positions per block of logits
V_BLOCKS = 16          # the head, upcast this many row blocks at a time

PARAM_NAMES = (
    "embed", "head", "final_norm", "in_norm", "pre_ff_norm", "q_w", "k_w",
    "v_w", "o_w", "in_proj_w", "conv_w", "conv_bias", "gate_norm",
    "out_proj_w", "mlp_gate_w", "mlp_up_w", "mlp_down_w")
_LAYER = PARAM_NAMES[3:]
FAULTS = (None, "fp8", "bf16_state", "no_ssm_carry", "no_conv_history",
          "no_attention")


def params_from_model(model):
    """The weights of a `FalconH1ForCausalLM` that the seed drew, by
    name."""
    held = model.param_arrays()
    return {n: held[n] for n in PARAM_NAMES}


def fixed_arrays(cfg):
    """(A_log, dt_bias, D) [heads] float32 from the configuration: A spread
    linearly over `A_initializer_range`, dt log-spaced over
    [`time_step_min`, `time_step_max`] and `dt_bias` the x with
    softplus(x) = dt, D `D_initial` in every head."""
    heads = int(cfg["mamba_n_heads"])
    lo, hi = (float(a) for a in cfg["A_initializer_range"])
    a = lo + (hi - lo) * np.arange(heads) / (heads - 1)
    dt = np.exp(np.log(float(cfg["time_step_min"])) + np.arange(heads)
                / (heads - 1) * np.log(float(cfg["time_step_max"])
                                       / float(cfg["time_step_min"])))
    return (jnp.asarray(np.log(a), jnp.float32),
            jnp.asarray(np.log(np.expm1(dt)), jnp.float32),
            jnp.full((heads,), float(cfg["D_initial"]), jnp.float32))


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


def _shape(cfg):
    """The statics of one layer, from the configuration file."""
    return dict(
        hq=int(cfg["num_attention_heads"]),
        hkv=int(cfg["num_key_value_heads"]), d=int(cfg["head_dim"]),
        eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]),
        heads=int(cfg["mamba_n_heads"]), p=int(cfg["mamba_d_head"]),
        groups=int(cfg["mamba_n_groups"]), n=int(cfg["mamba_d_state"]),
        ssm=int(cfg["mamba_d_ssm"]), chunk=int(cfg["mamba_chunk_size"]),
        mults=(float(cfg["attention_in_multiplier"]),
               float(cfg["key_multiplier"]),
               float(cfg["attention_out_multiplier"]),
               float(cfg["ssm_in_multiplier"]),
               float(cfg["ssm_out_multiplier"]),
               tuple(float(m) for m in cfg["ssm_multipliers"]),
               tuple(float(m) for m in cfg["mlp_multipliers"])))


@functools.partial(jax.jit, static_argnames=("hq", "hkv", "d", "eps",
                                             "theta", "mults", "fault"))
def _attention(x, p, *, hq, hkv, d, eps, theta, mults, fault):
    """The attention branch of one layer over x [S,H] (already normed)
    -> [S,H], its causal weights made by blocks of Q_BLOCK query rows."""
    a_in, k_mult, a_out = mults[:3]
    s = x.shape[0]
    a = x * a_in
    pos = jnp.arange(s)
    q = _rope(_mm(a, p["q_w"], fault).reshape(s, hq, d), pos, theta)
    k = _rope(_mm(a, p["k_w"], fault).reshape(s, hkv, d) * k_mult, pos,
              theta)
    v = _mm(a, p["v_w"], fault).reshape(s, hkv, d)
    pad = -s % Q_BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, Q_BLOCK, hkv, hq // hkv, d)
    j = jnp.arange(s)

    def block(args):
        i0, qq = args
        i = i0 + jnp.arange(Q_BLOCK)
        sc = jnp.einsum("qhgd,khd->hgqk", qq, k) / np.sqrt(d)
        sc = jnp.where((j[None, :] <= i[:, None])[None, None], sc, -jnp.inf)
        w = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", w, v)

    o = jax.lax.map(block, (jnp.arange(qb.shape[0]) * Q_BLOCK, qb))
    o = o.reshape(-1, hq * d)[:s]
    return _mm(o, p["o_w"], fault) * a_out


@functools.partial(jax.jit, static_argnames=(
    "heads", "p", "groups", "n", "ssm", "chunk", "eps", "mults", "fault"))
def _mixer(x, pr, fixed, upto, *, heads, p, groups, n, ssm, chunk, eps,
           mults, fault):
    """The Mamba-2 branch of one layer over x [S,H] (already normed) ->
    ([S,H], the SSM state [heads, p, n] after the first `upto` positions,
    the convolution's inputs [d_conv - 1, conv channels] before position
    `upto`): the state a position at a time."""
    s_in, s_out, zxbcdt_m = mults[3], mults[4], mults[5]
    s = x.shape[0]
    gn = groups * n
    sizes = (ssm, ssm, gn, gn, heads)
    mup = jnp.concatenate([jnp.full((k,), m, jnp.float32)
                           for k, m in zip(sizes, zxbcdt_m)])
    zxbcdt = _mm(x * s_in, pr["in_proj_w"], fault) * mup
    z = zxbcdt[:, :ssm]
    xbc = zxbcdt[:, ssm:2 * ssm + 2 * gn]
    a_log, dt_bias, d_skip = fixed
    dt = jax.nn.softplus(zxbcdt[:, 2 * ssm + 2 * gn:] + dt_bias)
    w = _f32(pr["conv_w"])                                 # [C, K]
    taps = w.shape[1]
    padded = jnp.pad(xbc, ((taps - 1, 0), (0, 0)))
    conv = sum(w[:, k] * padded[k:k + s] for k in range(taps)
               if fault != "no_conv_history" or k == taps - 1)
    xbc = jax.nn.silu(conv + _f32(pr["conv_bias"]))
    xs = xbc[:, :ssm].reshape(s, heads, p)
    bb = xbc[:, ssm:ssm + gn].reshape(s, groups, n)
    cc = xbc[:, ssm + gn:].reshape(s, groups, n)
    a = -jnp.exp(a_log)
    group_of = jnp.arange(heads) // (heads // groups)

    def step(carry, inp):
        state, kept = carry
        t, x_t, dt_t, b_t, c_t = inp
        if fault == "no_ssm_carry":
            state = jnp.where(t % chunk == 0, 0.0, state)
        state = (jnp.exp(dt_t * a)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[group_of][:, None])
        if fault == "bf16_state":
            # not a round trip through astype: XLA on a TPU may drop a
            # convert pair under its excess-precision rule
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        kept = jnp.where(t == upto - 1, state, kept)
        return (state, kept), jnp.einsum("hpn,hn->hp", state, c_t[group_of])

    zero = jnp.zeros((heads, p, n), jnp.float32)
    (_, kept), y = jax.lax.scan(step, (zero, zero),
                                (jnp.arange(s), xs, dt, bb, cc))
    y = y + d_skip[:, None] * xs
    g = y.reshape(s, ssm) * jax.nn.silu(z)
    g = g.reshape(s, groups, ssm // groups)
    g = g * jax.lax.rsqrt((g * g).mean(-1, keepdims=True) + eps)
    g = g.reshape(s, ssm) * _f32(pr["gate_norm"])
    # padded[k] is the input at position k - (taps - 1)
    inputs = jax.lax.dynamic_slice_in_dim(padded, upto, taps - 1)
    return _mm(g, pr["out_proj_w"], fault) * s_out, kept, inputs


@functools.partial(jax.jit, static_argnames=("eps", "mults", "fault"))
def _mlp(h, p, *, eps, mults, fault):
    gm, dm = mults[6]
    nn = _rms(h, p["pre_ff_norm"], eps)
    f = jax.nn.silu(_mm(nn, p["mlp_gate_w"], fault) * gm) \
        * _mm(nn, p["mlp_up_w"], fault)
    return h + _mm(f, p["mlp_down_w"], fault) * dm


def _layers(params, ids, cfg, fault, upto):
    """(hidden states [S, H] after the last layer, [(convolution inputs,
    SSM state) after the first `upto` positions, a layer each])."""
    assert fault in FAULTS, fault
    st = _shape(cfg)
    eps, mults = st["eps"], st["mults"]
    fixed = fixed_arrays(cfg)
    kept = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"][ids]) * float(cfg["embedding_multiplier"])
        for l in range(int(cfg["num_hidden_layers"])):
            p = {n: params[n][l] for n in _LAYER}
            xn = _rms(x, p["in_norm"], eps)
            ssm, state, inputs = _mixer(
                xn, p, fixed, jnp.int32(upto), heads=st["heads"], p=st["p"],
                groups=st["groups"], n=st["n"], ssm=st["ssm"],
                chunk=st["chunk"], eps=eps, mults=mults, fault=fault)
            kept.append((inputs, state))
            if fault != "no_attention":
                ssm = ssm + _attention(
                    xn, p, hq=st["hq"], hkv=st["hkv"], d=st["d"], eps=eps,
                    theta=st["theta"], mults=mults, fault=fault)
            x = _mlp(x + ssm, p, eps=eps, mults=mults, fault=fault)
        return x, kept


def hidden(params, ids, cfg, fault=None):
    """Float32 hidden states [S, H] of one sequence `ids` [S] after the
    last layer (before the final norm).  `fault`: the module docstring."""
    return _layers(params, ids, cfg, fault, len(ids))[0]


def states(params, ids, n, cfg, fault=None):
    """What a server keeps of the sequence `ids` [S] once it has taken its
    first `n` positions, a layer each: {"conv": the convolution's last
    `mamba_d_conv - 1` inputs [d_conv - 1, conv channels], oldest first,
    "ssm": the SSM state [heads, mamba_d_head, mamba_d_state]}, float32
    numpy.  Positions past `n` change neither, so `ids` may be padded."""
    kept = _layers(params, ids, cfg, fault, n)[1]
    return [{"conv": np.asarray(c), "ssm": np.asarray(s)} for c, s in kept]


@functools.partial(jax.jit, static_argnames=("eps", "mult", "fault"))
def _head_block(x, norm_w, head, *, eps, mult, fault):
    """Logits [P, V] of a block of positions, the head upcast a block of
    its rows at a time."""
    hn = _rms(x, norm_w, eps)
    edges = np.linspace(0, head.shape[0], V_BLOCKS + 1).astype(int)
    return jnp.concatenate(
        [_mm(hn, head[a:b].T, fault) for a, b in zip(edges, edges[1:])
         if b > a], axis=-1) * mult


def logits(params, ids, cfg, fault=None):
    """Float32 logits [S, V] of one sequence: for sequences whose logits
    fit (the tests'); `greedy_margins` never holds them whole."""
    x = hidden(params, ids, cfg, fault)
    with jax.default_matmul_precision("highest"):
        return _head_block(x, params["final_norm"], params["head"],
                           eps=float(cfg["rms_norm_eps"]),
                           mult=float(cfg["lm_head_multiplier"]),
                           fault=fault)


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
    mult = float(cfg["lm_head_multiplier"])
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
                                params["head"], eps=eps, mult=mult,
                                fault=fault),
                    nxt[a:a + P_BLOCK])
                ms.append(np.asarray(m))
                ss.append(np.asarray(s))
        n = len(row) - 1
        out_m.append(np.concatenate(ms)[:n])
        out_s.append(np.concatenate(ss)[:n])
    return np.stack(out_m), np.stack(out_s)
