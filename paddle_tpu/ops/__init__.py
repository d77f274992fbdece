"""Framework op namespace + Tensor method attachment.

Mirrors the reference's `python/paddle/tensor/__init__.py` pattern: ops are
plain functions; a registration step attaches them as Tensor methods and
installs the arithmetic/indexing dunder operators.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core.dispatch import apply
from ._inplace import _autograd_snapshot, _inplace_rebind, make_inplace

from .creation import *  # noqa: F401,F403
from .math import *  # noqa: F401,F403
from .manipulation import *  # noqa: F401,F403
from .logic import *  # noqa: F401,F403
from .array import *  # noqa: F401,F403
from .extras import *  # noqa: F401,F403

from . import array, creation, math, manipulation, logic, extras
# serving-side paged-KV primitives (the ragged op's XLA fallback and the
# pool writers): importable as ops.paged_attention — array-level only,
# deliberately NOT star-exported into the top-level paddle namespace
# (engine primitives, not user tensor ops)
from . import paged_attention  # noqa: F401
# low-bit quantized storage/compute primitives (paddle_tpu.lowbit's op
# layer) — array-level only, same non-export rationale as paged_attention
from . import lowbit  # noqa: F401
# fused ragged paged attention (the serving decode workhorse: one
# fixed-shape program with in-program cache update + int8 dequant) —
# array-level only, same non-export rationale as paged_attention
from . import ragged_paged_attention  # noqa: F401

__all__ = (
    list(creation.__all__)
    + list(math.__all__)
    + list(manipulation.__all__)
    + list(logic.__all__)
    + list(array.__all__)
    + list(extras.__all__)
)


# -- dunder operators -------------------------------------------------------

def _coerce(other):
    if isinstance(other, Tensor):
        return other
    return Tensor(jnp.asarray(other))


def _install_operators():
    from . import math as m, logic as lg

    def binop(fn):
        def op(self, other):
            return fn(self, _coerce(other))

        return op

    def rbinop(fn):
        def op(self, other):
            return fn(_coerce(other), self)

        return op

    Tensor.__add__ = binop(m.add)
    Tensor.__radd__ = rbinop(m.add)
    Tensor.__sub__ = binop(m.subtract)
    Tensor.__rsub__ = rbinop(m.subtract)
    Tensor.__mul__ = binop(m.multiply)
    Tensor.__rmul__ = rbinop(m.multiply)
    Tensor.__truediv__ = binop(m.divide)
    Tensor.__rtruediv__ = rbinop(m.divide)
    Tensor.__floordiv__ = binop(m.floor_divide)
    Tensor.__mod__ = binop(m.remainder)
    Tensor.__pow__ = binop(m.pow)
    Tensor.__rpow__ = rbinop(m.pow)
    Tensor.__matmul__ = binop(m.matmul)
    Tensor.__neg__ = lambda self: m.neg(self)
    Tensor.__abs__ = lambda self: m.abs(self)
    Tensor.__eq__ = lambda self, o: lg.equal(self, o)
    Tensor.__ne__ = lambda self, o: lg.not_equal(self, o)
    Tensor.__lt__ = lambda self, o: lg.less_than(self, o)
    Tensor.__le__ = lambda self, o: lg.less_equal(self, o)
    Tensor.__gt__ = lambda self, o: lg.greater_than(self, o)
    Tensor.__ge__ = lambda self, o: lg.greater_equal(self, o)
    Tensor.__invert__ = lambda self: lg.logical_not(self)


def _prep_index(item):
    """Normalize an indexing expression; Tensor indices become jax arrays."""
    if not isinstance(item, tuple):
        item = (item,)
    out = []
    for it in item:
        if isinstance(it, Tensor):
            arr = it._data
            if arr.dtype == jnp.bool_:
                # boolean mask → host advanced indexing (dynamic shape)
                out.append(jax.device_get(arr))
            else:
                out.append(arr)
        else:
            out.append(it)
    return tuple(out)


def _getitem(self, item):
    import builtins

    idx = _prep_index(item)
    import numpy as np

    if builtins.any(isinstance(i, np.ndarray) and i.dtype == bool for i in idx):
        # dynamic-shape path, non-jittable (same as reference masked_select)
        return Tensor(jnp.asarray(np.asarray(self._data)[
            tuple(np.asarray(i) if hasattr(i, "shape") else i for i in idx)
        ]))
    return apply(lambda a: a[idx], self, name="getitem")


def _setitem(self, item, value):
    idx = _prep_index(item)
    src = _autograd_snapshot(self)
    if isinstance(value, Tensor):
        out = apply(
            lambda a, v: a.at[idx].set(v.astype(a.dtype)), src, value, name="setitem"
        )
    else:
        out = apply(lambda a: a.at[idx].set(value), src, name="setitem")
    # In-place rebind (reference: __setitem__ is an inplace op on the eager
    # tensor; autograd-wise the tensor now points at the new producing node,
    # whose recorded input is the frozen snapshot).
    _inplace_rebind(self, out)


_METHODS = {}


def _install_methods():
    import types

    namespaces = [creation, math, manipulation, logic, extras]
    skip = {"zeros", "ones", "full", "empty", "arange", "linspace", "eye",
            "rand", "randn", "randint", "uniform", "normal", "randperm",
            "meshgrid", "assign"}
    for ns in namespaces:
        for name in ns.__all__:
            fn = getattr(ns, name)
            if name in skip or not callable(fn):
                continue
            if not hasattr(Tensor, name):
                setattr(Tensor, name, fn)
            _METHODS[name] = fn
    # aliases matching paddle.Tensor surface
    Tensor.astype = lambda self, dtype: manipulation.cast(self, dtype)
    Tensor.cast = lambda self, dtype: manipulation.cast(self, dtype)
    # reshape_/squeeze_/unsqueeze_/tanh_/scatter_ methods come from
    # ops.extras via the namespace loop above (single source of truth,
    # with full autograd rebinding — see extras._inplace_variant)
    Tensor.t = lambda self: manipulation.transpose(self, list(range(self.ndim))[::-1])
    Tensor.__getitem__ = _getitem
    Tensor.__setitem__ = _setitem
    Tensor.scale = lambda self, scale=1.0, bias=0.0, bias_after_scale=True: (
        apply(lambda a: a * scale + bias, self, name="scale")
        if bias_after_scale
        else apply(lambda a: (a + bias) * scale, self, name="scale")
    )
    Tensor.mean_ = Tensor.mean


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    out = apply(
        (lambda a: a * scale + bias) if bias_after_scale else (lambda a: (a + bias) * scale),
        x,
        name="scale",
    )
    if act == "relu":
        out = apply(lambda a: jnp.maximum(a, 0), out, name="relu")
    return out


def increment(x, value=1.0):
    out = apply(lambda a: a + value, x, name="increment")
    x._data = out._data
    return x


_install_operators()
_install_methods()
# linalg/inplace/random Tensor methods build ON the methods installed above
extras._attach_tensor_methods()

__all__ += ["scale", "increment"]
