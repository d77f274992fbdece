"""For kind `serve_family`: the model and its seeded weights, and the plain
reference, both named by the configuration's `harness`, so that another
architecture adds a configuration file and a reference module and no
runner.

  harness.constructor  'module:Class' of the program's configuration
                       object; it is given every top-level key of the file
                       that it has a field for (numbers, strings, lists:
                       the file is the configuration as it is run), and
                       harness.kwargs
  harness.model        'module:Class', built from that object
  harness.reference    module with params_from_model(model) and
                       greedy_margins(params, ids, config file) like
                       lib/reference_afmoe.py
  harness.dtype        the type the weights are made and served in
"""
import dataclasses

from benchmark.lib.common import fold_seed, resolve


def model_config(config):
    harness = config["harness"]
    ctor = resolve(harness["constructor"])
    fields = {f.name for f in dataclasses.fields(ctor)}
    stated = {k: v for k, v in config.items() if k in fields}
    return ctor(**stated, **harness["kwargs"])


def weight_rule(name):
    """How a parameter is made from the seed: norm scales 1 and selection
    biases 0 (the configuration's `assumed`), every matrix
    N(0, initializer_range)."""
    if "norm" in name:
        return "ones"
    if "bias" in name:
        return "zeros"
    return "normal"


def build_model(config, seed):
    """The harness's model with weights from `seed`: built under
    `LazyGuard` on the host, then every weight made on the default device
    in ONE jitted call in `harness.dtype`, a stacked weight one leading
    index at a time so that the float32 draw stays small.
    Returns (model, cfg)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework.compat import LazyGuard

    cfg = model_config(config)
    dtype = jnp.dtype(config["harness"]["dtype"])
    with jax.default_device(jax.devices("cpu")[0]), LazyGuard():
        model = resolve(config["harness"]["model"])(cfg)
    named = list(model.named_parameters())
    rules = [weight_rule(n) for n, _ in named]
    shapes = [tuple(p.shape) for _, p in named]
    std = float(cfg.initializer_range)

    def fill(key):
        out = []
        for i, (rule, shape) in enumerate(zip(rules, shapes)):
            if rule == "normal":
                keys = jax.random.split(jax.random.fold_in(key, i), shape[0])
                out.append(jax.lax.map(
                    lambda k, rest=shape[1:]: (jax.random.normal(
                        k, rest, jnp.float32) * std).astype(dtype), keys))
            else:
                out.append(jnp.full(shape, rule == "ones", dtype))
        return out

    weights = jax.jit(fill)(jax.random.PRNGKey(fold_seed(seed)))
    for (_, p), w in zip(named, weights):
        p._data = w
        p._lazy_init = None
    model.to(dtype=config["harness"]["dtype"])     # sets the layers' dtype
    return model, cfg


def reference(config):
    import importlib

    return importlib.import_module(config["harness"]["reference"])
