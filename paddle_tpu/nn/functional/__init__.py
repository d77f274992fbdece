"""nn.functional (reference: python/paddle/nn/functional/*).

Every function is a thin eager op over a pure jax forward; XLA fuses the
elementwise chains into the surrounding matmuls/convs (the role the
reference's hand-fused CUDA ops in operators/fused/ play is taken by the
compiler + the Pallas kernels in paddle_tpu/ops/pallas_ops.py).
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...core.dispatch import apply
from ...core.dtype import convert_dtype
from ...core import random as _rng
from ...autograd import tape

__all__ = [
    # activations
    "relu", "relu6", "gelu", "sigmoid", "tanh", "softmax", "log_softmax",
    "leaky_relu", "elu", "selu", "celu", "silu", "swish", "mish",
    "hardswish", "hardsigmoid", "hardtanh", "hardshrink", "softshrink",
    "tanhshrink", "softplus", "softsign", "prelu", "rrelu", "glu",
    "gumbel_softmax", "maxout", "thresholded_relu", "log_sigmoid",
    # linear / conv / pool
    "linear", "conv1d", "conv2d", "conv3d", "conv1d_transpose",
    "conv2d_transpose", "conv3d_transpose",
    "max_pool1d", "max_pool2d", "max_pool3d", "avg_pool1d", "avg_pool2d",
    "avg_pool3d", "adaptive_avg_pool1d", "adaptive_avg_pool2d",
    "adaptive_avg_pool3d", "adaptive_max_pool2d",
    # norm
    "batch_norm", "layer_norm", "instance_norm", "group_norm", "normalize",
    "local_response_norm", "rms_norm",
    # dropout & co
    "dropout", "dropout2d", "dropout3d", "alpha_dropout",
    # embedding / sparse
    "embedding", "one_hot",
    # losses
    "cross_entropy", "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss", "nll_loss",
    "smooth_l1_loss", "kl_div", "margin_ranking_loss", "hinge_embedding_loss",
    "cosine_embedding_loss", "ctc_loss", "log_loss", "square_error_cost",
    "sigmoid_focal_loss", "triplet_margin_loss", "poisson_nll_loss",
    # attention / transformer
    "scaled_dot_product_attention", "pad", "interpolate", "upsample",
    "pixel_shuffle", "pixel_unshuffle", "grid_sample", "affine_grid",
    "cosine_similarity", "label_smooth", "sequence_mask", "temporal_shift",
    "npair_loss", "fold", "channel_shuffle",
]


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

def _unary(fn, name):
    def op(x, name_=None):
        return apply(fn, x, name=name)

    op.__name__ = name
    return op


relu = _unary(lambda a: jnp.maximum(a, 0), "relu")
relu6 = _unary(lambda a: jnp.clip(a, 0, 6), "relu6")
sigmoid = _unary(jax.nn.sigmoid, "sigmoid")
tanh = _unary(jnp.tanh, "tanh")
silu = _unary(jax.nn.silu, "silu")
softsign = _unary(jax.nn.soft_sign, "softsign")
log_sigmoid = _unary(jax.nn.log_sigmoid, "log_sigmoid")
tanhshrink = _unary(lambda a: a - jnp.tanh(a), "tanhshrink")
mish = _unary(lambda a: a * jnp.tanh(jax.nn.softplus(a)), "mish")
hardswish = _unary(lambda a: a * jnp.clip(a + 3, 0, 6) / 6, "hardswish")


def gelu(x, approximate=False, name=None):
    return apply(
        lambda a: jax.nn.gelu(a, approximate=approximate), x, name="gelu"
    )


def softmax(x, axis=-1, dtype=None, name=None):
    def fn(a):
        if dtype is not None:
            a = a.astype(convert_dtype(dtype))
        return jax.nn.softmax(a, axis=axis)

    return apply(fn, x, name="softmax")


def log_softmax(x, axis=-1, dtype=None, name=None):
    def fn(a):
        if dtype is not None:
            a = a.astype(convert_dtype(dtype))
        return jax.nn.log_softmax(a, axis=axis)

    return apply(fn, x, name="log_softmax")


def leaky_relu(x, negative_slope=0.01, name=None):
    return apply(
        lambda a: jnp.where(a >= 0, a, negative_slope * a), x, name="leaky_relu"
    )


def elu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.elu(a, alpha), x, name="elu")


def selu(
    x,
    scale=1.0507009873554804934193349852946,
    alpha=1.6732632423543772848170429916717,
    name=None,
):
    return apply(
        lambda a: scale * jnp.where(a > 0, a, alpha * jnp.expm1(a)), x, name="selu"
    )


def celu(x, alpha=1.0, name=None):
    return apply(lambda a: jax.nn.celu(a, alpha), x, name="celu")


def swish(x, name=None):
    return silu(x)


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    return apply(
        lambda a: jnp.clip(slope * a + offset, 0, 1), x, name="hardsigmoid"
    )


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return apply(lambda a: jnp.clip(a, min, max), x, name="hardtanh")


def hardshrink(x, threshold=0.5, name=None):
    return apply(
        lambda a: jnp.where(jnp.abs(a) > threshold, a, 0.0), x, name="hardshrink"
    )


def softshrink(x, threshold=0.5, name=None):
    return apply(
        lambda a: jnp.where(
            a > threshold, a - threshold, jnp.where(a < -threshold, a + threshold, 0.0)
        ),
        x,
        name="softshrink",
    )


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return apply(
        lambda a: jnp.where(
            a * beta > threshold, a, (1.0 / beta) * jax.nn.softplus(beta * a)
        ),
        x,
        name="softplus",
    )


def prelu(x, weight, data_format="NCHW", name=None):
    def fn(a, w):
        if w.size == 1:
            wb = w.reshape(())
        else:
            shape = [1] * a.ndim
            ch_axis = 1 if data_format.startswith("NC") else a.ndim - 1
            shape[ch_axis] = w.size
            wb = w.reshape(shape)
        return jnp.where(a >= 0, a, wb * a)

    return apply(fn, x, weight, name="prelu")


def rrelu(x, lower=1.0 / 8, upper=1.0 / 3, training=True, name=None):
    if training:
        key = _rng.next_key()

        def fn(a):
            slope = jax.random.uniform(key, a.shape, jnp.float32, lower, upper).astype(a.dtype)
            return jnp.where(a >= 0, a, slope * a)

        return apply(fn, x, name="rrelu")
    mid = (lower + upper) / 2
    return leaky_relu(x, mid)


def thresholded_relu(x, threshold=1.0, name=None):
    return apply(
        lambda a: jnp.where(a > threshold, a, 0.0), x, name="thresholded_relu"
    )


def glu(x, axis=-1, name=None):
    def fn(a):
        a1, a2 = jnp.split(a, 2, axis=axis)
        return a1 * jax.nn.sigmoid(a2)

    return apply(fn, x, name="glu")


def maxout(x, groups, axis=1, name=None):
    def fn(a):
        ax = axis % a.ndim
        c = a.shape[ax]
        new_shape = a.shape[:ax] + (c // groups, groups) + a.shape[ax + 1:]
        return jnp.max(a.reshape(new_shape), axis=ax + 1)

    return apply(fn, x, name="maxout")


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None):
    key = _rng.next_key()

    def fn(a):
        g = jax.random.gumbel(key, a.shape, a.dtype)
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            # tie-safe straight-through one-hot of the argmax
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            y_hard = jnp.zeros_like(y)
            y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis, inplace=False) \
                if hasattr(jnp, "put_along_axis") else \
                (jnp.arange(a.shape[axis]).reshape([-1 if i == (axis % a.ndim) else 1 for i in range(a.ndim)]) == idx).astype(a.dtype)
            y = y_hard + y - jax.lax.stop_gradient(y)
        return y

    return apply(fn, x, name="gumbel_softmax")


# ---------------------------------------------------------------------------
# linear / conv / pool
# ---------------------------------------------------------------------------

def linear(x, weight, bias=None, name=None):
    """y = x @ W + b, W shaped [in, out] (reference convention,
    python/paddle/nn/functional/common.py:1783)."""
    if bias is None:
        return apply(lambda a, w: a @ w, x, weight, name="linear")
    return apply(lambda a, w, b: a @ w + b, x, weight, bias, name="linear")


def _tuplize(v, n):
    if isinstance(v, (list, tuple)):
        if len(v) == n:
            return tuple(int(i) for i in v)
        if len(v) == 1:
            return tuple(int(v[0]) for _ in range(n))
        return tuple(int(i) for i in v)
    return tuple(int(v) for _ in range(n))


def _conv_padding(padding, nd, strides=None):
    """Normalize paddle padding spec → lax padding list [(lo,hi)]*nd or str."""
    if isinstance(padding, str):
        return padding.upper()
    if isinstance(padding, int):
        return [(padding, padding)] * nd
    padding = list(padding)
    if len(padding) == nd and all(isinstance(p, int) for p in padding):
        return [(p, p) for p in padding]
    if len(padding) == 2 * nd:
        # [before0, after0, before1, after1...] paddle flat form
        return [(padding[2 * i], padding[2 * i + 1]) for i in range(nd)]
    if all(isinstance(p, (list, tuple)) for p in padding):
        return [tuple(p) for p in padding]
    raise ValueError(f"bad padding {padding}")


def _conv_nd(x, weight, bias, stride, padding, dilation, groups, nd, data_format, transpose=False, output_padding=0):
    strides = _tuplize(stride, nd)
    dils = _tuplize(dilation, nd)
    pad = _conv_padding(padding, nd)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    spatial = "DHW"[3 - nd:] if nd <= 3 else None
    if nd == 1:
        spec_in = "NCH" if not channel_last else "NHC"
        spec_k = "OIH"
        spec_out = spec_in
    elif nd == 2:
        spec_in = "NCHW" if not channel_last else "NHWC"
        spec_k = "OIHW"
        spec_out = spec_in
    else:
        spec_in = "NCDHW" if not channel_last else "NDHWC"
        spec_k = "OIDHW"
        spec_out = spec_in
    dn = jax.lax.conv_dimension_numbers((1,) * (nd + 2), (1,) * (nd + 2), (spec_in, spec_k, spec_out))

    def fn(a, w, *maybe_b):
        # AMP convention: the weight dtype defines compute precision, so a
        # fp32 input meeting bf16 params (model.bfloat16()) rides the MXU in
        # bf16 instead of erroring in lax.conv_general_dilated.
        if a.dtype != w.dtype and jnp.issubdtype(w.dtype, jnp.floating):
            a = a.astype(w.dtype)
        if transpose:
            opad = _tuplize(output_padding, nd)
            if isinstance(pad, str):
                pads = pad
            else:
                # conv_transpose pad semantics: effective output crop
                k_eff = [dils[i] * (w.shape[2 + i] - 1) + 1 for i in range(nd)]
                pads = [
                    (k_eff[i] - 1 - pad[i][0], k_eff[i] - 1 - pad[i][1] + opad[i])
                    for i in range(nd)
                ]
            if groups > 1:
                # w is [cin, cout/g, k...]; the equivalent forward conv
                # needs [cout, cin/g, k...] with the swap done PER GROUP
                # (a plain swapaxes mixes channels across groups and
                # trips conv_general_dilated's feature-count check)
                ci, cog = w.shape[0], w.shape[1]
                wt = w.reshape((groups, ci // groups, cog) + w.shape[2:])
                wt = jnp.swapaxes(wt, 1, 2).reshape(
                    (groups * cog, ci // groups) + w.shape[2:])
            else:
                wt = jnp.swapaxes(w, 0, 1)  # I O ... for transpose
            wt = jnp.flip(wt, axis=tuple(range(2, 2 + nd)))
            out = jax.lax.conv_general_dilated(
                a,
                wt,
                window_strides=(1,) * nd,
                padding=pads if not isinstance(pads, str) else pads,
                lhs_dilation=strides,
                rhs_dilation=dils,
                dimension_numbers=dn,
                feature_group_count=groups,
            )
        else:
            out = jax.lax.conv_general_dilated(
                a,
                w,
                window_strides=strides,
                padding=pad,
                rhs_dilation=dils,
                dimension_numbers=dn,
                feature_group_count=groups,
            )
        if maybe_b:
            b = maybe_b[0]
            shape = [1] * out.ndim
            ch_axis = 1 if not channel_last else out.ndim - 1
            shape[ch_axis] = b.size
            out = out + b.reshape(shape)
        return out

    args = (x, weight) if bias is None else (x, weight, bias)
    return apply(fn, *args, name=f"conv{nd}d{'_transpose' if transpose else ''}")


def conv1d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCL", name=None):
    fmt = "NLC" if data_format == "NLC" else "NCH"
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, 1, fmt)


def conv2d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCHW", name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, 2, data_format)


def conv3d(x, weight, bias=None, stride=1, padding=0, dilation=1, groups=1, data_format="NCDHW", name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, 3, data_format)


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, dilation=1, groups=1, data_format="NCL", name=None):
    fmt = "NLC" if data_format == "NLC" else "NCH"
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, 1, fmt, transpose=True, output_padding=output_padding)


def conv2d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, dilation=1, groups=1, data_format="NCHW", name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, 2, data_format, transpose=True, output_padding=output_padding)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0, output_padding=0, dilation=1, groups=1, data_format="NCDHW", name=None):
    return _conv_nd(x, weight, bias, stride, padding, dilation, groups, 3, data_format, transpose=True, output_padding=output_padding)


def _pool_nd(x, kernel, stride, padding, nd, op, data_format, ceil_mode=False, exclusive=True):
    """exclusive=True (paddle default): padded zeros are NOT counted in avg
    denominators; ceil_mode pads the high side so partial windows are kept."""
    ks = _tuplize(kernel, nd)
    st = _tuplize(stride if stride is not None else kernel, nd)
    pad = _conv_padding(padding, nd)
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    if isinstance(pad, str):
        if pad == "VALID":
            pad = [(0, 0)] * nd
        else:  # "SAME": resolve numerically so every downstream branch
               # (ceil extras, inclusive divisors) sees explicit pairs
            spatial_d = x.shape[1:-1] if channel_last else x.shape[2:]
            pad = []
            for i in range(nd):
                total = max((-(-spatial_d[i] // st[i]) - 1) * st[i]
                            + ks[i] - spatial_d[i], 0)
                pad.append((total // 2, total - total // 2))
    pad_base = list(pad)  # pre-ceil pads
    if ceil_mode:
        spatial = x.shape[1:-1] if channel_last else x.shape[2:]
        pad = [
            (lo, hi + _ceil_extra(spatial[i], ks[i], st[i], lo, hi))
            for i, (lo, hi) in enumerate(pad)
        ]
    if channel_last:
        window = (1,) + ks + (1,)
        strides = (1,) + st + (1,)
        pads = [(0, 0)] + list(pad) + [(0, 0)]
    else:
        window = (1, 1) + ks
        strides = (1, 1) + st
        pads = [(0, 0), (0, 0)] + list(pad)

    def fn(a):
        if op == "max":
            init = -jnp.inf if jnp.issubdtype(a.dtype, jnp.floating) else jnp.iinfo(a.dtype).min
            return jax.lax.reduce_window(a, init, jax.lax.max, window, strides, pads)
        # avg / sum
        s = jax.lax.reduce_window(a, 0.0, jax.lax.add, window, strides, pads)
        if op == "sum":
            return s   # divisor_override applies its own divisor
        if not exclusive and not ceil_mode:
            # every window's padded extent is exactly k (PoolOutputSize
            # guarantees hstart+k <= H+pad for floor-mode windows)
            return s / float(np.prod(ks))
        if exclusive:
            ones = jnp.ones_like(a)
            cnt = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window,
                                        strides, pads)
            # a ceil window fully inside padding has zero valid elements;
            # the reference divides 0 by a non-positive extent yielding
            # +-0 — clamp to keep the same finite value without the NaN
            return s / jnp.maximum(cnt, 1.0)
        # inclusive + ceil: reference pooling.cc:84 — the divisor is the
        # window clipped to input + ORIGINAL pad on the high side (left
        # pad rows count, the ceil extra does not). Static per-axis
        # extents broadcast-multiplied.
        spatial = a.shape[1:-1] if channel_last else a.shape[2:]
        div = None
        for i in range(nd):
            lo, hi = pad[i]
            hi0 = pad_base[i][1]              # pre-ceil high pad
            out_i = (spatial[i] + lo + hi - ks[i]) // st[i] + 1
            starts = np.arange(out_i) * st[i] - lo
            ends = np.minimum(starts + ks[i], spatial[i] + hi0)
            ext = np.maximum((ends - starts).astype(np.float32), 1.0)
            shape = [1] * a.ndim
            shape[(1 if channel_last else 2) + i] = out_i
            e = jnp.asarray(ext).reshape(shape)
            div = e if div is None else div * e
        return s / div

    return apply(fn, x, name=f"{op}_pool{nd}d")


def _ceil_extra(size, k, s, lo, hi):
    """Extra high-side padding so the output size matches ceil division.

    PADDLE semantics (the parity contract): plain ceil division —
    reference PoolOutputSize (phi/kernels/funcs/pooling.h:368) KEEPS a
    window that starts inside the right padding. torch drops it; the
    torch-differential tests restrict ceil comparisons to shapes where
    the two agree."""
    floor_out = (size + lo + hi - k) // s + 1
    ceil_out = -((size + lo + hi - k) // -s) + 1
    return (ceil_out - floor_out) * s


def _max_pool_mask(x, ks, st, pads_2d):
    """Window-argmax indices (global H*W flat index, paddle return_mask
    semantics) via conv_general_dilated_patches."""

    def fn(a):
        n, c, h, w = a.shape
        patches = jax.lax.conv_general_dilated_patches(
            a, ks, st, pads_2d, dimension_numbers=("NCHW", "OIHW", "NCHW")
        )  # [N, C*kh*kw, OH, OW]
        oh, ow = patches.shape[2], patches.shape[3]
        patches = patches.reshape(n, c, ks[0] * ks[1], oh, ow)
        # padded cells (patches zero-fills them) must not win the argmax
        starts_i = jnp.arange(oh) * st[0] - pads_2d[0][0]
        starts_j = jnp.arange(ow) * st[1] - pads_2d[1][0]
        ri = starts_i[:, None] + jnp.arange(ks[0])[None, :]      # [oh, kh]
        rj = starts_j[:, None] + jnp.arange(ks[1])[None, :]      # [ow, kw]
        vi = (ri >= 0) & (ri < h)
        vj = (rj >= 0) & (rj < w)
        valid = vi[:, None, :, None] & vj[None, :, None, :]      # [oh,ow,kh,kw]
        valid = valid.transpose(2, 3, 0, 1).reshape(
            1, 1, ks[0] * ks[1], oh, ow)
        patches = jnp.where(valid, patches, -jnp.inf)
        arg = jnp.argmax(patches, axis=2)  # in-window flat idx
        # convert to global flat H*W index
        base_i = starts_i[None, None, :, None]
        base_j = starts_j[None, None, None, :]
        di = arg // ks[1]
        dj = arg % ks[1]
        gi = jnp.clip(base_i + di, 0, h - 1)
        gj = jnp.clip(base_j + dj, 0, w - 1)
        return (gi * w + gj).astype(jnp.int32)

    return Tensor(fn(x._data))


def max_pool1d(x, kernel_size, stride=None, padding=0, ceil_mode=False, return_mask=False, data_format="NCL", name=None):
    return _pool_nd(x, kernel_size, stride, padding, 1, "max",
                    "NLC" if data_format == "NLC" else "NCH",
                    ceil_mode=ceil_mode)


def max_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, return_mask=False, data_format="NCHW", name=None):
    out = _pool_nd(x, kernel_size, stride, padding, 2, "max", data_format, ceil_mode=ceil_mode)
    if return_mask:
        ks = _tuplize(kernel_size, 2)
        st = _tuplize(stride if stride is not None else kernel_size, 2)
        pad = _conv_padding(padding, 2)
        if isinstance(pad, str):
            pad = [(0, 0), (0, 0)]
        channel_last = data_format == "NHWC"
        xm = x.transpose([0, 3, 1, 2]) if channel_last else x
        if ceil_mode:
            # the mask must cover the same (possibly ceil-extended)
            # window grid as the pooled output
            spatial = xm.shape[2:]
            pad = [(lo, hi + _ceil_extra(spatial[i], ks[i], st[i], lo, hi))
                   for i, (lo, hi) in enumerate(pad)]
        mask = _max_pool_mask(xm, ks, st, pad)
        if channel_last:
            mask = mask.transpose([0, 2, 3, 1])
        return out, mask
    return out


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False, return_mask=False, data_format="NCDHW", name=None):
    return _pool_nd(x, kernel_size, stride, padding, 3, "max", data_format, ceil_mode=ceil_mode)


def avg_pool1d(x, kernel_size, stride=None, padding=0, exclusive=True, ceil_mode=False, data_format="NCL", name=None):
    return _pool_nd(x, kernel_size, stride, padding, 1, "avg",
                    "NLC" if data_format == "NLC" else "NCH",
                    ceil_mode=ceil_mode, exclusive=exclusive)


def avg_pool2d(x, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True, divisor_override=None, data_format="NCHW", name=None):
    if divisor_override:
        # window SUM / divisor: rescaling an inclusive average is wrong
        # whenever ceil_mode clips a window (its inclusive divisor is the
        # clipped extent, not k^2)
        s = _pool_nd(x, kernel_size, stride, padding, 2, "sum", data_format,
                     ceil_mode=ceil_mode)
        return s * (1.0 / float(divisor_override))
    return _pool_nd(x, kernel_size, stride, padding, 2, "avg", data_format, ceil_mode=ceil_mode, exclusive=exclusive)


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False, exclusive=True, divisor_override=None, data_format="NCDHW", name=None):
    return _pool_nd(x, kernel_size, stride, padding, 3, "avg", data_format, ceil_mode=ceil_mode, exclusive=exclusive)


def _adaptive_pool_core(a, out_sizes, op, spatial_start=2):
    """Pure-array adaptive pooling (shared by the adaptive_*_pool ops and
    interpolate's 'area' mode): per-axis reshape-reduce when divisible,
    else explicit [floor(j*n/os), ceil((j+1)*n/os)) window gather."""
    out = a
    for i, os in enumerate(out_sizes):
        ax = spatial_start + i
        n = out.shape[ax]
        if os is None:
            continue
        if n % os == 0:
            k = n // os
            new_shape = out.shape[:ax] + (os, k) + out.shape[ax + 1:]
            r = out.reshape(new_shape)
            out = jnp.max(r, axis=ax + 1) if op == "max" else jnp.mean(r, axis=ax + 1)
        else:
            idx = [
                (int(math.floor(j * n / os)), int(math.ceil((j + 1) * n / os)))
                for j in range(os)
            ]
            slices = []
            for lo, hi in idx:
                sl = jax.lax.slice_in_dim(out, lo, hi, axis=ax)
                red = jnp.max(sl, axis=ax, keepdims=True) if op == "max" else jnp.mean(sl, axis=ax, keepdims=True)
                slices.append(red)
            out = jnp.concatenate(slices, axis=ax)
    return out


def _adaptive_pool(x, output_size, nd, op, data_format):
    out_sizes = _tuplize(output_size, nd)
    # channel-last: spatial axes start right after batch
    start = 1 if data_format in ("NHWC", "NLC", "NDHWC") else 2

    def fn(a):
        return _adaptive_pool_core(a, out_sizes, op, spatial_start=start)

    return apply(fn, x, name=f"adaptive_{op}_pool{nd}d")


def adaptive_avg_pool1d(x, output_size, data_format="NCL", name=None):
    return _adaptive_pool(x, output_size, 1, "avg",
                          "NLC" if data_format == "NLC" else "NCH")


def adaptive_avg_pool2d(x, output_size, data_format="NCHW", name=None):
    return _adaptive_pool(x, output_size, 2, "avg", data_format)


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    return _adaptive_pool(x, output_size, 3, "avg", data_format)


def adaptive_max_pool2d(x, output_size, return_mask=False,
                        data_format="NCHW", name=None):
    if not return_mask:
        return _adaptive_pool(x, output_size, 2, "max", data_format)
    # mask = flat H*W index of each window's argmax (reference
    # max_pool_with_index semantics)
    out_sizes = _tuplize(output_size, 2)
    channel_last = data_format == "NHWC"

    def fn(a):
        if channel_last:
            a = jnp.moveaxis(a, -1, 1)   # NHWC -> NCHW internally
        N, C, H, W = a.shape
        oh = out_sizes[0] if out_sizes[0] is not None else H
        ow = out_sizes[1] if out_sizes[1] is not None else W
        out_rows, idx_rows = [], []
        for i in range(oh):
            h0, h1 = (i * H) // oh, -((-(i + 1) * H) // oh)
            out_cols, idx_cols = [], []
            for j in range(ow):
                w0, w1 = (j * W) // ow, -((-(j + 1) * W) // ow)
                win = a[:, :, h0:h1, w0:w1]
                kh, kw = h1 - h0, w1 - w0
                flat = win.reshape(N, C, kh * kw)
                out_cols.append(jnp.max(flat, axis=-1))
                am = jnp.argmax(flat, axis=-1)
                gidx = (h0 + am // kw) * W + (w0 + am % kw)
                idx_cols.append(gidx)
            out_rows.append(jnp.stack(out_cols, axis=-1))
            idx_rows.append(jnp.stack(idx_cols, axis=-1))
        out = jnp.stack(out_rows, axis=-2)               # [N, C, oh, ow]
        idx = jnp.stack(idx_rows, axis=-2).astype(jnp.int32)
        if channel_last:
            out = jnp.moveaxis(out, 1, -1)
            idx = jnp.moveaxis(idx, 1, -1)
        return out, idx

    return apply(fn, x, name="adaptive_max_pool2d")


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------

def layer_norm_arrays(a, w, b, naxes=(-1,), epsilon=1e-5):
    """Array-level LayerNorm body — THE normalization arithmetic of
    F.layer_norm (fp32 stats via jnp.mean/jnp.var).  Exposed so compiled
    paths that must match Layer-based models bitwise (the serving
    engine's final LN vs `GPTModel.ln_f`) share this exact op sequence
    instead of hand-copying it."""
    mu = jnp.mean(a.astype(jnp.float32), axis=naxes, keepdims=True)
    var = jnp.var(a.astype(jnp.float32), axis=naxes, keepdims=True)
    out = (a.astype(jnp.float32) - mu) * jax.lax.rsqrt(var + epsilon)
    out = out.astype(a.dtype)
    if w is not None:
        out = out * w
    if b is not None:
        out = out + b
    return out


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    naxes = tuple(range(-len(normalized_shape), 0))

    def fn(a, *wb):
        i = 0
        w = b = None
        if weight is not None:
            w = wb[i]
            i += 1
        if bias is not None:
            b = wb[i]
        return layer_norm_arrays(a, w, b, naxes, epsilon)

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply(fn, *args, name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm — capability-gap fill (absent in reference; table stakes for
    modern LLM families)."""

    def fn(a, *w):
        var = jnp.mean(jnp.square(a.astype(jnp.float32)), axis=-1, keepdims=True)
        out = (a.astype(jnp.float32) * jax.lax.rsqrt(var + epsilon)).astype(a.dtype)
        if w:
            out = out * w[0]
        return out

    args = (x,) if weight is None else (x, weight)
    return apply(fn, *args, name="rms_norm")


def batch_norm(
    x,
    running_mean,
    running_var,
    weight=None,
    bias=None,
    training=False,
    momentum=0.9,
    epsilon=1e-5,
    data_format="NCHW",
    use_global_stats=None,
    name=None,
):
    channel_last = data_format in ("NHWC", "NLC", "NDHWC")
    use_batch_stats = training and not use_global_stats

    def reduce_axes(a):
        ch_axis = a.ndim - 1 if channel_last else 1
        return tuple(i for i in range(a.ndim) if i != ch_axis), ch_axis

    if use_batch_stats:
        def fn(a, *wb):
            axes, ch = reduce_axes(a)
            af = a.astype(jnp.float32)
            mu = jnp.mean(af, axis=axes)
            var = jnp.var(af, axis=axes)
            shape = [1] * a.ndim
            shape[ch] = a.shape[ch]
            out = (af - mu.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
            out = out.astype(a.dtype)
            i = 0
            if weight is not None:
                out = out * wb[i].reshape(shape)
                i += 1
            if bias is not None:
                out = out + wb[i].reshape(shape)
            return out, mu, var

        args = [x]
        if weight is not None:
            args.append(weight)
        if bias is not None:
            args.append(bias)
        out, mu, var = apply(fn, *args, name="batch_norm")
        # update running stats in place (eager buffer semantics; under jit
        # tracing the buffer's ._data becomes a tracer captured as an output)
        with tape.no_grad():
            rm = running_mean._data.astype(jnp.float32)
            rv = running_var._data.astype(jnp.float32)
            running_mean._data = (momentum * rm + (1 - momentum) * mu._data).astype(running_mean.dtype)
            running_var._data = (momentum * rv + (1 - momentum) * var._data).astype(running_var.dtype)
        return out

    def fn_eval(a, m, v, *wb):
        ch = a.ndim - 1 if channel_last else 1
        shape = [1] * a.ndim
        shape[ch] = a.shape[ch]
        out = (a.astype(jnp.float32) - m.astype(jnp.float32).reshape(shape)) * jax.lax.rsqrt(
            v.astype(jnp.float32).reshape(shape) + epsilon
        )
        out = out.astype(a.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x, running_mean, running_var]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply(fn_eval, *args, name="batch_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None, use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW", name=None):
    def fn(a, *wb):
        axes = tuple(range(2, a.ndim))
        af = a.astype(jnp.float32)
        mu = jnp.mean(af, axis=axes, keepdims=True)
        var = jnp.var(af, axis=axes, keepdims=True)
        out = ((af - mu) * jax.lax.rsqrt(var + eps)).astype(a.dtype)
        i = 0
        if weight is not None:
            shape = [1, a.shape[1]] + [1] * (a.ndim - 2)
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            shape = [1, a.shape[1]] + [1] * (a.ndim - 2)
            out = out + wb[i].reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply(fn, *args, name="instance_norm")


def group_norm(x, num_groups, epsilon=1e-5, weight=None, bias=None, data_format="NCHW", name=None):
    def fn(a, *wb):
        n, c = a.shape[0], a.shape[1]
        g = num_groups
        rest = a.shape[2:]
        r = a.reshape((n, g, c // g) + rest).astype(jnp.float32)
        axes = tuple(range(2, r.ndim))
        mu = jnp.mean(r, axis=axes, keepdims=True)
        var = jnp.var(r, axis=axes, keepdims=True)
        out = ((r - mu) * jax.lax.rsqrt(var + epsilon)).reshape(a.shape).astype(a.dtype)
        shape = [1, c] + [1] * (a.ndim - 2)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [x]
    if weight is not None:
        args.append(weight)
    if bias is not None:
        args.append(bias)
    return apply(fn, *args, name="group_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    return apply(
        lambda a: a / jnp.maximum(
            jnp.linalg.norm(a, ord=p, axis=axis, keepdims=True), epsilon
        ),
        x,
        name="normalize",
    )


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    def fn(a):
        sq = jnp.square(a)
        half = size // 2
        c = a.shape[1]
        pads = [(0, 0), (half, size - 1 - half)] + [(0, 0)] * (a.ndim - 2)
        sqp = jnp.pad(sq, pads)
        acc = sum(
            jax.lax.slice_in_dim(sqp, i, i + c, axis=1) for i in range(size)
        )
        return a / jnp.power(k + alpha * acc / size, beta)

    return apply(fn, x, name="local_response_norm")


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------

def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training and p > 0.0:
            return apply(lambda a: a * (1.0 - p), x, name="dropout")
        return x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))
    key = _rng.next_key()

    def fn(a):
        shape = list(a.shape)
        if axis is not None:
            axes = axis if isinstance(axis, (list, tuple)) else [axis]
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)

    return apply(fn, x, name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    ax = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=ax, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    ax = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=ax, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x
    key = _rng.next_key()
    alpha = 1.6732632423543772848170429916717
    scale = 1.0507009873554804934193349852946
    alpha_p = -alpha * scale

    def fn(a):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        q = 1.0 - p
        a_coef = (q + alpha_p**2 * q * p) ** -0.5
        b_coef = -a_coef * alpha_p * p
        return (a_coef * jnp.where(keep, a, alpha_p) + b_coef).astype(a.dtype)

    return apply(fn, x, name="alpha_dropout")


# ---------------------------------------------------------------------------
# embedding
# ---------------------------------------------------------------------------

def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    # indices ride as a real op input (not a closure constant) so graph
    # recordings — static Program replay, onnx export — see the data edge
    def fn(idx, w):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None:
            mask = (idx == padding_idx)[..., None]
            out = jnp.where(mask, 0.0, out)
        return out

    return apply(fn, x, weight, name="embedding")


def one_hot(x, num_classes, name=None):
    from ...ops.manipulation import one_hot as _oh

    return _oh(x, num_classes)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def _reduce_loss(loss, reduction):
    if reduction == "mean":
        return jnp.mean(loss)
    if reduction == "sum":
        return jnp.sum(loss)
    return loss


def cross_entropy(
    input,
    label,
    weight=None,
    ignore_index=-100,
    reduction="mean",
    soft_label=False,
    axis=-1,
    use_softmax=True,
    label_smoothing=0.0,
    name=None,
):
    lbl = label._data if isinstance(label, Tensor) else jnp.asarray(label)

    def fn(logits, *w):
        nclass = logits.shape[axis]
        if soft_label:
            if use_softmax:
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=axis)
            else:
                logp = jnp.log(jnp.maximum(logits.astype(jnp.float32), 1e-30))
            tgt = lbl.astype(jnp.float32)
            loss = -jnp.sum(tgt * logp, axis=axis)
        else:
            # Hard labels: loss = logsumexp(logits) - logits[label]. The fp32
            # cast feeds straight into reductions/gathers, so XLA never
            # materializes an fp32 [.., V] log-prob or one-hot tensor — on a
            # 50k vocab that is GBs of HBM traffic per step (the bench's
            # single largest non-matmul cost before this formulation).
            li = lbl
            if li.ndim == logits.ndim:
                li = jnp.squeeze(li, axis=axis)
            li_clipped = jnp.clip(li, 0, nclass - 1)
            picked = jnp.squeeze(
                jnp.take_along_axis(
                    logits, jnp.expand_dims(li_clipped, axis), axis=axis),
                axis).astype(jnp.float32)
            if use_softmax:
                lse = jax.scipy.special.logsumexp(
                    logits.astype(jnp.float32), axis=axis)
                nll = lse - picked
                if label_smoothing > 0.0:
                    mean_logit = jnp.mean(
                        logits.astype(jnp.float32), axis=axis)
                    smooth = lse - mean_logit
                    nll = (1.0 - label_smoothing) * nll + label_smoothing * smooth
            else:
                logpicked = jnp.log(jnp.maximum(picked, 1e-30))
                nll = -logpicked
                if label_smoothing > 0.0:
                    logp_all = jnp.log(
                        jnp.maximum(logits.astype(jnp.float32), 1e-30))
                    smooth = -jnp.mean(logp_all, axis=axis)
                    nll = (1.0 - label_smoothing) * nll + label_smoothing * smooth
            valid = li != ignore_index
            loss = jnp.where(valid, nll, 0.0)
            if w:
                wt = jnp.take(w[0], li_clipped)
                loss = loss * wt
            if reduction == "mean":
                if w:
                    denom = jnp.sum(jnp.where(valid, jnp.take(w[0], li_clipped), 0.0))
                else:
                    denom = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
                return jnp.sum(loss) / denom
        return _reduce_loss(loss, reduction)

    args = (input,) if weight is None else (input, weight)
    return apply(fn, *args, name="cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False, ignore_index=-100, axis=-1, return_softmax=False):
    loss = cross_entropy(
        logits, label, soft_label=soft_label, ignore_index=ignore_index,
        reduction="none", axis=axis,
    )
    from ...ops.manipulation import unsqueeze

    if not soft_label and loss.ndim < logits.ndim:
        loss = unsqueeze(loss, axis)
    if return_softmax:
        return loss, softmax(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    def fn(p, t, *w):
        pf = jnp.clip(p.astype(jnp.float32), 1e-12, 1 - 1e-7)
        loss = -(t * jnp.log(pf) + (1 - t) * jnp.log(1 - pf))
        if w:
            loss = loss * w[0]
        return _reduce_loss(loss, reduction)

    args = [input, label if isinstance(label, Tensor) else Tensor(jnp.asarray(label))]
    if weight is not None:
        args.append(weight)
    return apply(fn, *args, name="bce")


def binary_cross_entropy_with_logits(logit, label, weight=None, reduction="mean", pos_weight=None, name=None):
    def fn(z, t, *extra):
        zf = z.astype(jnp.float32)
        tf_ = t.astype(jnp.float32)
        if pos_weight is not None:
            pw_arr = extra[-1]
            base = (1 - tf_) * zf + (1 + (pw_arr - 1) * tf_) * (
                jnp.log1p(jnp.exp(-jnp.abs(zf))) + jnp.maximum(-zf, 0)
            )
        else:
            base = jnp.maximum(zf, 0) - zf * tf_ + jnp.log1p(jnp.exp(-jnp.abs(zf)))
        if weight is not None:
            base = base * extra[0]
        return _reduce_loss(base, reduction)

    args = [logit, label if isinstance(label, Tensor) else Tensor(jnp.asarray(label))]
    if weight is not None:
        args.append(weight)
    if pos_weight is not None:
        args.append(pos_weight)
    return apply(fn, *args, name="bce_logits")


def mse_loss(input, label, reduction="mean", name=None):
    return apply(
        lambda a, b: _reduce_loss(jnp.square(a - b), reduction),
        input,
        label if isinstance(label, Tensor) else Tensor(jnp.asarray(label)),
        name="mse_loss",
    )


def l1_loss(input, label, reduction="mean", name=None):
    return apply(
        lambda a, b: _reduce_loss(jnp.abs(a - b), reduction),
        input,
        label if isinstance(label, Tensor) else Tensor(jnp.asarray(label)),
        name="l1_loss",
    )


def square_error_cost(input, label):
    return apply(lambda a, b: jnp.square(a - b), input, label, name="square_error_cost")


def log_loss(input, label, epsilon=1e-4, name=None):
    return apply(
        lambda p, t: -t * jnp.log(p + epsilon) - (1 - t) * jnp.log(1 - p + epsilon),
        input,
        label,
        name="log_loss",
    )


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean", name=None):
    lbl = label._data if isinstance(label, Tensor) else jnp.asarray(label)

    def fn(logp, *w):
        nclass = logp.shape[1]
        li = jnp.clip(lbl, 0, nclass - 1)
        oh = jax.nn.one_hot(li, nclass, axis=1, dtype=logp.dtype)
        loss = -jnp.sum(oh * logp, axis=1)
        valid = lbl != ignore_index
        loss = jnp.where(valid, loss, 0.0)
        if w:
            wt = jnp.take(w[0], li)
            loss = loss * wt
            if reduction == "mean":
                return jnp.sum(loss) / jnp.sum(jnp.where(valid, wt, 0.0))
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
        return _reduce_loss(loss, reduction)

    args = (input,) if weight is None else (input, weight)
    return apply(fn, *args, name="nll_loss")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def fn(a, b):
        d = a - b
        ad = jnp.abs(d)
        loss = jnp.where(ad < delta, 0.5 * d * d / delta, ad - 0.5 * delta)
        return _reduce_loss(loss, reduction)

    return apply(fn, input, label, name="smooth_l1_loss")


def kl_div(input, label, reduction="mean", name=None):
    def fn(logp, t):
        loss = t * (jnp.log(jnp.maximum(t, 1e-30)) - logp)
        if reduction == "batchmean":
            return jnp.sum(loss) / logp.shape[0]
        return _reduce_loss(loss, reduction)

    return apply(fn, input, label, name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean", name=None):
    return apply(
        lambda a, b, t: _reduce_loss(jnp.maximum(-t * (a - b) + margin, 0.0), reduction),
        input,
        other,
        label,
        name="margin_ranking_loss",
    )


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return apply(
        lambda a, t: _reduce_loss(
            jnp.where(t == 1, a, jnp.maximum(0.0, margin - a)), reduction
        ),
        input,
        label,
        name="hinge_embedding_loss",
    )


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean", name=None):
    def fn(a, b, t):
        cos = jnp.sum(a * b, axis=-1) / (
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1) + 1e-12
        )
        loss = jnp.where(t == 1, 1 - cos, jnp.maximum(0.0, cos - margin))
        return _reduce_loss(loss, reduction)

    return apply(fn, input1, input2, label, name="cosine_embedding_loss")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0, reduction="sum", name=None):
    def fn(z, t, *n):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * t + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * t + (1 - p) * (1 - t)
        a_t = alpha * t + (1 - alpha) * (1 - t)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if n:
            loss = loss / n[0]
        return _reduce_loss(loss, reduction)

    args = [logit, label]
    if normalizer is not None:
        args.append(normalizer)
    return apply(fn, *args, name="sigmoid_focal_loss")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0, epsilon=1e-6, swap=False, reduction="mean", name=None):
    def fn(a, pos, neg):
        dp = jnp.linalg.norm(a - pos + epsilon, ord=p, axis=-1)
        dn = jnp.linalg.norm(a - neg + epsilon, ord=p, axis=-1)
        if swap:
            dn2 = jnp.linalg.norm(pos - neg + epsilon, ord=p, axis=-1)
            dn = jnp.minimum(dn, dn2)
        return _reduce_loss(jnp.maximum(dp - dn + margin, 0.0), reduction)

    return apply(fn, input, positive, negative, name="triplet_margin_loss")


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8, reduction="mean", name=None):
    def fn(a, t):
        if log_input:
            loss = jnp.exp(a) - t * a
        else:
            loss = a - t * jnp.log(a + epsilon)
        if full:
            stirling = t * jnp.log(t + epsilon) - t + 0.5 * jnp.log(2 * jnp.pi * (t + epsilon))
            loss = loss + jnp.where(t > 1, stirling, 0.0)
        return _reduce_loss(loss, reduction)

    return apply(fn, input, label, name="poisson_nll_loss")


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def fn(a, p, l):
        sim = a @ p.T
        tgt = (l[:, None] == l[None, :]).astype(jnp.float32)
        tgt = tgt / jnp.sum(tgt, axis=1, keepdims=True)
        logp = jax.nn.log_softmax(sim, axis=1)
        ce = -jnp.mean(jnp.sum(tgt * logp, axis=1))
        reg = l2_reg * (jnp.mean(jnp.sum(a * a, axis=1)) + jnp.mean(jnp.sum(p * p, axis=1))) * 0.25
        return ce + reg

    return apply(fn, anchor, positive, labels, name="npair_loss")


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0, reduction="mean", norm_by_times=False):
    """CTC (reference: warpctc op) — dynamic-programming formulation in lax.scan."""
    lp = log_probs._data if isinstance(log_probs, Tensor) else jnp.asarray(log_probs)
    lbl = labels._data if isinstance(labels, Tensor) else jnp.asarray(labels)
    il = input_lengths._data if isinstance(input_lengths, Tensor) else jnp.asarray(input_lengths)
    ll = label_lengths._data if isinstance(label_lengths, Tensor) else jnp.asarray(label_lengths)

    def fn(logits):
        # logits: [T, B, C] (paddle convention max_logit_length first)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        T, B, C = logp.shape
        L = lbl.shape[1]
        S = 2 * L + 1
        # extended labels with blanks
        ext = jnp.full((B, S), blank, dtype=lbl.dtype)
        ext = ext.at[:, 1::2].set(lbl)
        neg_inf = -1e30

        init = jnp.full((B, S), neg_inf)
        init = init.at[:, 0].set(logp[0, :, blank])
        init = init.at[:, 1].set(
            jnp.take_along_axis(logp[0], ext[:, 1:2], axis=1)[:, 0]
        )

        same_as_prev2 = jnp.concatenate(
            [jnp.ones((B, 2), bool), ext[:, 2:] == ext[:, :-2]], axis=1
        )

        def step(alpha, logp_t):
            a0 = alpha
            a1 = jnp.concatenate([jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
            a2 = jnp.concatenate([jnp.full((B, 2), neg_inf), alpha[:, :-2]], axis=1)
            a2 = jnp.where(same_as_prev2, neg_inf, a2)
            m = jnp.maximum(jnp.maximum(a0, a1), a2)
            m_safe = jnp.where(m == neg_inf, 0.0, m)
            merged = m_safe + jnp.log(
                jnp.exp(a0 - m_safe) + jnp.exp(a1 - m_safe) + jnp.exp(a2 - m_safe) + 1e-37
            )
            merged = jnp.where(m == neg_inf, neg_inf, merged)
            emit = jnp.take_along_axis(logp_t, ext, axis=1)
            return merged + emit, merged + emit

        alpha_T, alphas = jax.lax.scan(step, init, logp[1:])
        all_alphas = jnp.concatenate([init[None], alphas], axis=0)  # [T,B,S]
        # gather at t = il-1, s in {2*ll, 2*ll-1}
        t_idx = jnp.clip(il - 1, 0, T - 1)
        per_b = all_alphas[t_idx, jnp.arange(B)]  # [B, S]
        s1 = jnp.clip(2 * ll, 0, S - 1)
        s2 = jnp.clip(2 * ll - 1, 0, S - 1)
        v1 = jnp.take_along_axis(per_b, s1[:, None], axis=1)[:, 0]
        v2 = jnp.take_along_axis(per_b, s2[:, None], axis=1)[:, 0]
        m = jnp.maximum(v1, v2)
        m_safe = jnp.where(m == neg_inf, 0.0, m)
        ll_total = m_safe + jnp.log(jnp.exp(v1 - m_safe) + jnp.exp(v2 - m_safe))
        loss = -ll_total
        if reduction == "mean":
            return jnp.mean(loss / jnp.maximum(ll.astype(jnp.float32), 1.0))
        return _reduce_loss(loss, reduction)

    return apply(fn, log_probs, name="ctc_loss")


# ---------------------------------------------------------------------------
# attention & misc
# ---------------------------------------------------------------------------

def scaled_dot_product_attention(
    query, key, value, attn_mask=None, dropout_p=0.0, is_causal=False, training=True, name=None
):
    """Fused attention entry point. On TPU this routes to the Pallas flash
    kernel when shapes allow (paddle_tpu/ops/pallas_ops.py); fallback is the
    XLA softmax composition. Layout: [batch, seq, heads, head_dim]
    (reference convention for fused_attention, operators/fused/)."""
    from ...ops import pallas_ops

    return pallas_ops.flash_attention(
        query, key, value, attn_mask=attn_mask, dropout_p=dropout_p,
        is_causal=is_causal, training=training,
    )


def pad(x, pad, mode="constant", value=0.0, data_format="NCHW", name=None):
    if isinstance(pad, Tensor):
        pad = [int(v) for v in pad.numpy()]
    pad = [int(p) for p in pad]

    def fn(a):
        nd = a.ndim
        if len(pad) == 2 * nd:
            pairs = [(pad[2 * i], pad[2 * i + 1]) for i in range(nd)]
        else:
            # paddle flat spec: first pair pads the LAST spatial dim
            # ([left, right, top, bottom] for NCHW)
            k = len(pad) // 2
            spatial = [(pad[2 * i], pad[2 * i + 1]) for i in range(k)][::-1]
            if data_format in ("NCHW", "NCL", "NCDHW", "NCH"):
                pairs = [(0, 0), (0, 0)] + spatial
            else:
                pairs = [(0, 0)] + spatial + [(0, 0)]
        jmode = {"constant": "constant", "reflect": "reflect", "replicate": "edge", "circular": "wrap"}[mode]
        if jmode == "constant":
            return jnp.pad(a, pairs, mode="constant", constant_values=value)
        return jnp.pad(a, pairs, mode=jmode)

    return apply(fn, x, name="pad")


def _resize_positions(ins, outs, align_corners, align_mode):
    """Source sampling positions per output index (reference
    interp_kernels' coordinate transforms): corner-aligned
    i*(in-1)/(out-1); else align_mode 0 = half-pixel (i+0.5)*scale-0.5,
    align_mode 1 = i*scale."""
    if align_corners:
        if outs == 1:
            return jnp.zeros((1,), jnp.float32)
        return jnp.arange(outs, dtype=jnp.float32) * ((ins - 1) / (outs - 1))
    scale = ins / outs
    if align_mode == 1:
        return jnp.arange(outs, dtype=jnp.float32) * scale
    pos = (jnp.arange(outs, dtype=jnp.float32) + 0.5) * scale - 0.5
    return jnp.maximum(pos, 0.0)


def _resize_axis_linear(a, ax, outs, align_corners, align_mode):
    ins = a.shape[ax]
    pos = _resize_positions(ins, outs, align_corners, align_mode)
    lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, ins - 1)
    hi = jnp.minimum(lo + 1, ins - 1)
    w = (pos - lo.astype(jnp.float32)).astype(a.dtype)
    shape = [1] * a.ndim
    shape[ax] = outs
    wb = w.reshape(shape)
    return (jnp.take(a, lo, axis=ax) * (1 - wb)
            + jnp.take(a, hi, axis=ax) * wb)


def _resize_axis_cubic(a, ax, outs, align_corners):
    """4-tap Keys cubic, A=-0.75 (reference bicubic_interp kernel — the
    same coefficient as the CUDA `cubic_convolution` helpers), taps
    edge-clamped, NO antialiasing on downscale (jax.image.resize's cubic
    antialiases, which the reference op does not)."""
    ins = a.shape[ax]
    pos = _resize_positions(ins, outs, align_corners, 0)
    if not align_corners:
        # cubic keeps the raw half-pixel position (may be < 0 at i=0)
        pos = (jnp.arange(outs, dtype=jnp.float32) + 0.5) * (ins / outs) - 0.5
    i0 = jnp.floor(pos).astype(jnp.int32)
    t = (pos - i0.astype(jnp.float32))
    A = -0.75

    def k1(tt):     # |t| <= 1
        return ((A + 2.0) * tt - (A + 3.0)) * tt * tt + 1.0

    def k2(tt):     # 1 < |t| < 2
        return ((A * tt - 5.0 * A) * tt + 8.0 * A) * tt - 4.0 * A

    weights = [k2(t + 1.0), k1(t), k1(1.0 - t), k2(2.0 - t)]
    shape = [1] * a.ndim
    shape[ax] = outs
    out = None
    for off, w in zip((-1, 0, 1, 2), weights):
        idx = jnp.clip(i0 + off, 0, ins - 1)
        term = jnp.take(a, idx, axis=ax) * w.reshape(shape).astype(a.dtype)
        out = term if out is None else out + term
    return out


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False, align_mode=0, data_format="NCHW", name=None):
    if mode not in ("nearest", "linear", "bilinear", "trilinear", "bicubic",
                    "area"):
        raise ValueError(f"unsupported interpolate mode {mode!r}")
    channel_last = data_format in ("NHWC", "NWC", "NLC", "NDHWC")
    ax0 = 1 if channel_last else 2           # first spatial axis

    def fn(a):
        in_spatial = (a.shape[1:-1] if channel_last else a.shape[2:])
        if size is not None:
            out_spatial = tuple(int(s) for s in (size if isinstance(size, (list, tuple)) else [size]))
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) else [scale_factor] * len(in_spatial)
            out_spatial = tuple(int(s * f) for s, f in zip(in_spatial, sf))
        if mode == "area":
            # reference: 'area' is adaptive average pooling — the shared
            # pure core (spatial axes start at 1 for channel-last)
            return _adaptive_pool_core(a, out_spatial, "avg",
                                       spatial_start=ax0)
        out = a
        for i, (ins, outs) in enumerate(zip(in_spatial, out_spatial)):
            ax = ax0 + i
            if mode == "nearest":
                # reference NearestNeighborInterpolate: floor(ratio*i)
                # with ratio in/out, or round(ratio*i) with corner-
                # aligned ratio (in-1)/(out-1) (interpolate_kernel.cc:210)
                if align_corners and outs > 1:
                    r = (ins - 1) / (outs - 1)
                    idx = jnp.floor(jnp.arange(outs) * r + 0.5).astype(jnp.int32)
                else:
                    idx = jnp.floor(jnp.arange(outs) * (ins / outs)).astype(jnp.int32)
                out = jnp.take(out, jnp.clip(idx, 0, ins - 1), axis=ax)
            elif mode == "bicubic":
                out = _resize_axis_cubic(out, ax, outs, align_corners)
            else:  # linear / bilinear / trilinear
                out = _resize_axis_linear(out, ax, outs, align_corners,
                                          align_mode)
        return out

    return apply(fn, x, name="interpolate")


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False, align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode, data_format)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor

    def fn(a):
        n, c, h, w = a.shape
        oc = c // (r * r)
        out = a.reshape(n, oc, r, r, h, w)
        out = out.transpose(0, 1, 4, 2, 5, 3)
        return out.reshape(n, oc, h * r, w * r)

    return apply(fn, x, name="pixel_shuffle")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = downscale_factor

    def fn(a):
        n, c, h, w = a.shape
        out = a.reshape(n, c, h // r, r, w // r, r)
        out = out.transpose(0, 1, 3, 5, 2, 4)
        return out.reshape(n, c * r * r, h // r, w // r)

    return apply(fn, x, name="pixel_unshuffle")


def channel_shuffle(x, groups, data_format="NCHW", name=None):
    def fn(a):
        n, c, h, w = a.shape
        out = a.reshape(n, groups, c // groups, h, w)
        out = out.transpose(0, 2, 1, 3, 4)
        return out.reshape(n, c, h, w)

    return apply(fn, x, name="channel_shuffle")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros", align_corners=True, name=None):
    def fn(a, g):
        n, c, h, w = a.shape
        gx = g[..., 0]
        gy = g[..., 1]
        if align_corners:
            ix = (gx + 1) * (w - 1) / 2
            iy = (gy + 1) * (h - 1) / 2
        else:
            ix = ((gx + 1) * w - 1) / 2
            iy = ((gy + 1) * h - 1) / 2

        def sample(img, yy, xx):
            # img [C,H,W]; yy,xx [Ho,Wo]
            x0 = jnp.floor(xx).astype(jnp.int32)
            y0 = jnp.floor(yy).astype(jnp.int32)
            x1, y1 = x0 + 1, y0 + 1

            def gather(yi, xi):
                valid = (yi >= 0) & (yi < h) & (xi >= 0) & (xi < w)
                yc = jnp.clip(yi, 0, h - 1)
                xc = jnp.clip(xi, 0, w - 1)
                vals = img[:, yc, xc]  # [C,Ho,Wo]
                return jnp.where(valid, vals, 0.0)

            wa = (x1 - xx) * (y1 - yy)
            wb = (xx - x0) * (y1 - yy)
            wc = (x1 - xx) * (yy - y0)
            wd = (xx - x0) * (yy - y0)
            if mode == "nearest":
                return gather(jnp.round(yy).astype(jnp.int32), jnp.round(xx).astype(jnp.int32))
            return (
                gather(y0, x0) * wa + gather(y0, x1) * wb + gather(y1, x0) * wc + gather(y1, x1) * wd
            )

        out = jax.vmap(sample)(a, iy, ix)
        return out.astype(a.dtype)

    return apply(fn, x, grid, name="grid_sample")


def affine_grid(theta, out_shape, align_corners=True, name=None):
    def fn(th):
        n, _, h, w = [int(s) for s in out_shape] if len(out_shape) == 4 else (int(out_shape[0]), 0, int(out_shape[1]), int(out_shape[2]))
        if align_corners:
            ys = jnp.linspace(-1, 1, h)
            xs = jnp.linspace(-1, 1, w)
        else:
            ys = (jnp.arange(h) * 2 + 1) / h - 1
            xs = (jnp.arange(w) * 2 + 1) / w - 1
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=-1)  # [H,W,3]
        out = jnp.einsum("hwk,nck->nhwc", base, th)
        return out

    return apply(fn, theta, name="affine_grid")


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def fn(a, b):
        dot = jnp.sum(a * b, axis=axis)
        na = jnp.linalg.norm(a, axis=axis)
        nb = jnp.linalg.norm(b, axis=axis)
        return dot / jnp.maximum(na * nb, eps)

    return apply(fn, x1, x2, name="cosine_similarity")


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def fn(l, *pd):
        k = l.shape[-1]
        if pd:
            return (1 - epsilon) * l + epsilon * pd[0]
        return (1 - epsilon) * l + epsilon / k

    args = (label,) if prior_dist is None else (label, prior_dist)
    return apply(fn, *args, name="label_smooth")


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    lengths = x._data if isinstance(x, Tensor) else jnp.asarray(x)
    ml = int(maxlen) if maxlen is not None else int(jnp.max(lengths))
    out = (jnp.arange(ml)[None, :] < lengths[..., None]).astype(convert_dtype(dtype))
    return Tensor(out)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    def fn(a):
        nt, c, h, w = a.shape
        n = nt // seg_num
        r = a.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        left = jnp.concatenate([r[:, 1:, :fold], jnp.zeros_like(r[:, :1, :fold])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(r[:, :1, fold:2 * fold]), r[:, :-1, fold:2 * fold]], axis=1)
        rest = r[:, :, 2 * fold:]
        out = jnp.concatenate([left, right, rest], axis=2)
        return out.reshape(nt, c, h, w)

    return apply(fn, x, name="temporal_shift")


def fold(x, output_sizes, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """col2im inverse of unfold."""
    os = _tuplize(output_sizes, 2)
    ks = _tuplize(kernel_sizes, 2)
    st = _tuplize(strides, 2)
    pd = _tuplize(paddings, 2)
    dl = _tuplize(dilations, 2)

    def fn(a):
        n, ckk, l = a.shape
        c = ckk // (ks[0] * ks[1])
        ph, pw = os[0] + 2 * pd[0], os[1] + 2 * pd[1]
        oh = (ph - (dl[0] * (ks[0] - 1) + 1)) // st[0] + 1
        ow = (pw - (dl[1] * (ks[1] - 1) + 1)) // st[1] + 1
        r = a.reshape(n, c, ks[0], ks[1], oh, ow)
        out = jnp.zeros((n, c, ph, pw), a.dtype)
        for i in range(ks[0]):
            for j in range(ks[1]):
                di, dj = i * dl[0], j * dl[1]
                out = out.at[:, :, di : di + oh * st[0] : st[0], dj : dj + ow * st[1] : st[1]].add(r[:, :, i, j])
        return out[:, :, pd[0] : pd[0] + os[0], pd[1] : pd[1] + os[1]]

    return apply(fn, x, name="fold")


from .extras import *  # noqa: E402,F401,F403
from .extras import __all__ as _extras_all  # noqa: E402

__all__ += list(_extras_all)
