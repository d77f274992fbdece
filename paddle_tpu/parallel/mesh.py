"""Device mesh management.

Reference analog: fleet/base/topology.py:56 CommunicateTopology — a
cartesian rank topology over axes ["data","pipe","sharding","model"] with an
NCCL group per axis slice. Here the same topology is ONE
jax.sharding.Mesh; "groups" are named axes and XLA compiles collectives
onto the physical ICI torus (device order comes from jax.devices(), which
is already topology-sorted for TPU).
"""
from __future__ import annotations

import threading
from typing import Dict, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

_state = threading.local()

AXIS_ORDER = ("dp", "sharding", "pp", "ep", "sp", "mp")


def _current():
    return getattr(_state, "mesh", None)


def init_mesh(dp=1, mp=1, pp=1, sharding=1, sp=1, ep=1, devices=None) -> Mesh:
    """Build + install the global hybrid-parallel mesh.

    Axis order puts dp outermost and mp innermost so tensor-parallel
    collectives ride the fastest ICI links (reference fleet orders
    [data, pipe, sharding, model] for the same reason — topology.py:56).
    """
    devices = list(devices if devices is not None else jax.devices())
    need = dp * mp * pp * sharding * sp * ep
    if need > len(devices):
        raise ValueError(
            f"mesh {dp}x{sharding}x{pp}x{ep}x{sp}x{mp}={need} exceeds {len(devices)} devices"
        )
    devices = devices[:need]
    arr = np.array(devices).reshape(dp, sharding, pp, ep, sp, mp)
    mesh = Mesh(arr, ("dp", "sharding", "pp", "ep", "sp", "mp"))
    _state.mesh = mesh
    return mesh


def set_mesh(mesh: Mesh):
    _state.mesh = mesh


def get_mesh() -> Optional[Mesh]:
    m = _current()
    if m is None:
        # default: trivial 1-axis mesh over all devices on 'dp'.  Kept
        # apart from the installed mesh: `_current()` stays None until
        # init_mesh/set_mesh, which is how a kernel call site tells a
        # program the user sharded from one that merely ran on a
        # multi-device host
        m = getattr(_state, "default", None)
        if m is None:
            devs = np.array(jax.devices()).reshape(-1, 1, 1, 1, 1, 1)
            m = _state.default = Mesh(devs, AXIS_ORDER)
    return m


def mesh_axes():
    return get_mesh().axis_names


def axis_size(name: str) -> int:
    mesh = get_mesh()
    if name not in mesh.axis_names:
        return 1
    return mesh.shape[name]


def has_axis(name: str) -> bool:
    return axis_size(name) > 1


class MeshGuard:
    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self):
        self._prev = _current()
        _state.mesh = self.mesh
        return self.mesh

    def __exit__(self, *exc):
        _state.mesh = self._prev
        return False
