"""paddle_tpu — a TPU-native deep-learning framework.

A ground-up re-design of the reference framework's capabilities
(KevinKDA-Resources/Paddle, surveyed in SURVEY.md) for TPU hardware:

- eager Tensors ride jax.Array / XLA's async runtime (no hand-written
  allocator/stream stack — that is the hardware-native runtime here),
- autograd records jax.vjp pullbacks (no per-op gradient kernel zoo),
- the blessed performance path is whole-graph compilation (`paddle_tpu.jit`),
- distributed training is SPMD over a `jax.sharding.Mesh` with XLA
  collectives on ICI/DCN (no NCCL, no comm-id bootstrap),
- hot kernels (attention, fused FFN) are Pallas.

The public API mirrors the reference's `paddle.*` surface so users can
switch with minimal churn.
"""
from __future__ import annotations

__version__ = "0.1.0"

import os as _os

if _os.environ.get("PTPU_FORCE_PLATFORM"):
    # launcher/spawn children must pin the backend BEFORE first jax use
    # (a chip belongs to one process; the launcher sets this for children
    # that must stay off it)
    import jax as _jax

    _jax.config.update("jax_platforms", _os.environ["PTPU_FORCE_PLATFORM"])

from .core.tensor import Tensor, TracedValueError, to_tensor
from .core.containers import SelectedRows, StringTensor
from .core.dtype import (
    bool_,
    uint8,
    int8,
    int16,
    int32,
    int64,
    float16,
    bfloat16,
    float32,
    float64,
    complex64,
    complex128,
)
from .core.random import seed
from .core import random as _rng

from .ops import *  # noqa: F401,F403
from .ops import __all__ as _ops_all

from .autograd import no_grad, enable_grad, grad, set_grad_enabled, is_grad_enabled
from . import autograd
from . import ops

__all__ = ["Tensor", "TracedValueError", "to_tensor", "seed", "no_grad",
           "grad"] + list(_ops_all)

# Subsystems (populated progressively; import order matters — nn/optimizer
# build on ops; monitor first — it is stdlib-only and the others report
# telemetry through it).
from . import monitor  # noqa: E402
from . import framework  # noqa: E402
from . import device  # noqa: E402
from . import nn  # noqa: E402
from . import optimizer  # noqa: E402
from . import io  # noqa: E402
from . import amp  # noqa: E402
from . import jit  # noqa: E402
from . import static  # noqa: E402
from . import metric  # noqa: E402
from . import vision  # noqa: E402
from . import distributed  # noqa: E402
from . import resilience  # noqa: E402
from . import incubate  # noqa: E402
from . import utils  # noqa: E402
from . import profiler  # noqa: E402
from . import linalg  # noqa: E402
from . import hapi  # noqa: E402
from .hapi import Model, summary  # noqa: E402
from . import distribution  # noqa: E402
from . import fft  # noqa: E402
from . import signal  # noqa: E402
from . import sparse  # noqa: E402
from . import quantization  # noqa: E402
from . import lowbit  # noqa: E402
from . import geometric  # noqa: E402
from . import text  # noqa: E402
from . import audio  # noqa: E402
from . import inference  # noqa: E402
from . import hub  # noqa: E402
from . import reader  # noqa: E402
from . import dataset  # noqa: E402
from .reader import batch  # noqa: E402
from . import sysconfig  # noqa: E402
from . import onnx  # noqa: E402
from .cost_model import CostModel  # noqa: E402

from .framework.io_ import save, load  # noqa: E402
from .framework.core_ import (  # noqa: E402
    set_default_dtype,
    get_default_dtype,
    set_flags,
    get_flags,
    get_rng_state,
    set_rng_state,
)
from .framework.compat import (  # noqa: E402
    CPUPlace, CUDAPlace, CUDAPinnedPlace, NPUPlace, XPUPlace, CustomPlace,
    iinfo, finfo, set_printoptions, disable_signal_handler, LazyGuard, flops,
)
from .device import set_device, get_device  # noqa: E402
from .nn.layer import ParamAttr  # noqa: E402
from .distributed import DataParallel  # noqa: E402
from .core.dtype import bool_ as bool  # noqa: E402,A001  (reference exports `paddle.bool`)

import numpy as _np  # noqa: E402
dtype = _np.dtype  # paddle.dtype: the dtype class (np.dtype on XLA)
# rng-state aliases: one counter-based PRNG serves every backend (the
# reference separates host and CUDA generator stacks; XLA has one)
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    """Free-function parameter creation (reference
    python/paddle/tensor/creation.py:create_parameter)."""
    from .nn.layer import Layer, ParamAttr

    if name is not None:
        attr = ParamAttr._to_attr(attr)
        if attr is not False and attr.name is None:
            attr.name = name
    holder = Layer()
    return holder.create_parameter(shape, attr=attr, dtype=dtype,
                                   is_bias=is_bias,
                                   default_initializer=default_initializer)

disable_static = static.disable_static
enable_static = static.enable_static
in_dynamic_mode = static.in_dynamic_mode

__all__ += ["save", "load", "set_default_dtype", "get_default_dtype", "set_device", "get_device", "Model", "summary"]
