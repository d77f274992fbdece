"""Test configuration: run everything on a virtual 8-device CPU mesh
(SURVEY §4 takeaway (b): single-host multi-process parity tests → here,
XLA CPU multi-device stands in for a TPU pod).

Must run before jax initializes its backend: the suite is CPU-only, also
on a host that has a chip.
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
# Child processes spawned by tests (DataLoader workers, store rendezvous,
# launcher pods) import paddle_tpu WITHOUT this conftest; the env var makes
# paddle_tpu/__init__ pin their backend to CPU too (a chip belongs to one
# process at a time, so a child must never reach for it).
os.environ["PTPU_FORCE_PLATFORM"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import warnings

import numpy as np
import pytest

warnings.filterwarnings(
    "ignore", message=".*dtype int64 requested.*", category=UserWarning
)


@pytest.fixture(autouse=True, scope="module")
def _no_mesh_left_by_another_file():
    """Every test FILE starts with no mesh installed, as a process of its
    own would.  Files that call `init_mesh` leave their mesh behind
    (`tests/test_collective_api.py` leaves dp=4), and a flash-attention
    test of batch 2 that a worker happens to run after one fails on it;
    which file follows which depends on xdist's schedule, which every new
    test file moves (PR 32: 14 tests of test_flash_mask.py)."""
    from paddle_tpu import parallel

    parallel.set_mesh(None)
    yield


@pytest.fixture(autouse=True)
def _seed():
    import random

    import paddle_tpu as paddle

    paddle.seed(1234)
    np.random.seed(1234)
    # the legacy reader decorators (paddle.reader.shuffle) draw from the
    # global `random` module; unseeded, their batch order depends on
    # whatever ran earlier in the session and the loss-decrease asserts in
    # test_reader_dataset/test_examples become order-flaky
    random.seed(1234)
    yield
