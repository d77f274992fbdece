"""A runner counts every compile of the process inside its window, and its
window starts with a `monitor.snapshot()`.  A callback gauge that an earlier
test file of the same worker left behind is computed at the next scrape
(`optimizer/grad_norm` reduces the last eager step's gradients, which
compiles six small programs), so scrape once before each benchmark test:
what is pending is settled outside any window."""
import pytest


@pytest.fixture(autouse=True)
def _settled_monitor():
    from paddle_tpu import monitor

    monitor.snapshot()
