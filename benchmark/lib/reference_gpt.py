"""Plain reference of the GPT-2/GPT-3 decoder (Radford et al. 2019; Brown
et al. 2020): learned positions, pre-LayerNorm blocks, full causal
multi-head attention, tanh-GELU MLP, final LayerNorm, output head tied to
the token embedding.  `jax.numpy` in float32 under
`default_matmul_precision("highest")`; no kernel, no cache, no batching:
one sequence at a time (`lax.map`), one layer's weights upcast at a time
(`lax.scan` over the stacked weights), so no second copy of the model is
ever held.

`params` is a dict of arrays in whatever type the system holds them:
  wte [V,H]  wpe [P,H]  lnf_w lnf_b [H]
  and, stacked over layers, ln1_w ln1_b ln2_w ln2_b [L,H]  qkv_w [L,H,3H]
  qkv_b [L,3H]  out_w [L,H,H]  out_b [L,H]  fc_in_w [L,H,I]  fc_in_b [L,I]
  fc_out_w [L,I,H]  fc_out_b [L,H]
The QKV projection's 3H outputs are laid out [3, heads, head_dim].
"""
import functools
import math

import jax
import jax.numpy as jnp

STACKED = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
           "ln2_w", "ln2_b", "fc_in_w", "fc_in_b", "fc_out_w", "fc_out_b")


def params_from_model(model):
    """The arrays of a `GPTForCausalLM(stacked_blocks=True)`, by name."""
    gpt = model.gpt
    out = {"wte": gpt.embeddings.word_embeddings.weight._data,
           "wpe": gpt.embeddings.position_embeddings.weight._data,
           "lnf_w": gpt.ln_f.weight._data, "lnf_b": gpt.ln_f.bias._data}
    for name in STACKED:
        out[name] = getattr(gpt.blocks, name)._data
    return out


def _f32(x):
    return x.astype(jnp.float32)


def _layer_norm(x, w, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(h, p, heads, eps):
    s, hidden = h.shape
    hd = hidden // heads
    p = {k: _f32(v) for k, v in p.items()}       # this layer only
    x = _layer_norm(h, p["ln1_w"], p["ln1_b"], eps)
    qkv = (x @ p["qkv_w"] + p["qkv_b"]).reshape(s, 3, heads, hd)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
    scores = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    att = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("hqk,khd->qhd", att, v).reshape(s, hidden)
    h = h + o @ p["out_w"] + p["out_b"]
    x = _layer_norm(h, p["ln2_w"], p["ln2_b"], eps)
    m = _gelu_tanh(x @ p["fc_in_w"] + p["fc_in_b"])
    return h + m @ p["fc_out_w"] + p["fc_out_b"]


def _logits_one(params, ids, heads, eps):
    s = ids.shape[0]
    h = _f32(params["wte"][ids]) + _f32(params["wpe"][:s])
    stacked = {k: params[k] for k in STACKED}
    h, _ = jax.lax.scan(
        lambda c, p: (_block(c, p, heads, eps), None), h, stacked)
    h = _layer_norm(h, _f32(params["lnf_w"]), _f32(params["lnf_b"]), eps)
    return h @ _f32(params["wte"]).T


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def mean_cross_entropy(params, ids, labels, *, heads, eps):
    """Mean over every position of every row of -log softmax(logits)[label]
    (labels are given per position: nothing is shifted here)."""
    def row(args):
        x, y = args
        logits = _logits_one(params, x, heads, eps)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(row, (ids, labels)).mean()


@functools.partial(jax.jit, static_argnames=("heads", "eps"))
def greedy_margins(params, ids, *, heads, eps):
    """For rows of token ids [N,S]: at each position p < S-1, how far the
    reference logit of the token that actually follows (ids[p+1]) lies
    below the largest logit of that position, and the standard deviation
    of that position's logits.  Returns two [N,S-1] float32 arrays."""
    def row(x):
        logits = _logits_one(params, x, heads, eps)[:-1]
        chosen = jnp.take_along_axis(logits, x[1:, None], axis=-1)[:, 0]
        return logits.max(-1) - chosen, logits.std(-1)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(row, ids)
