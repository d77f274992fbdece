"""The observability tax with every layer off: < 1 µs a call.

Five hot-path calls, each timed with its layer disabled: a span, a
serving phase, a counter increment, a perf segment, and the rpc
path's trace inject+extract.  Tier-1 pins what makes them cheap (each
returns the module's shared no-op object or returns at its gate, and
records nothing: tests/test_trace.py, test_monitor.py, test_perf.py,
test_fleet.py).  The wall-clock bound lives here, in the slow tier,
because six parallel test workers on a shared host can push a
microsecond loop past it on a CPU that is merely busy.
"""
import time

import pytest

from paddle_tpu import monitor
from paddle_tpu.monitor import perf, trace


def _span():
    def call(i):
        with trace.span("t/overhead", step=i):
            pass
    return call


def _phase():
    def call(i):
        with trace.phase("t/overhead"):
            pass
    return call


def _counter_inc():
    c = monitor.counter("t/overhead")   # held, as the hot paths hold it
    return lambda i: c.inc()


def _perf_segment():
    def call(i):
        with perf.segment("t", "overhead"):
            pass
    return call


def _inject_extract():
    return lambda i: trace.extract(trace.inject())


@pytest.fixture
def all_off():
    was = (monitor.enabled(), trace.enabled(), perf.enabled())
    monitor.enable(False)
    trace.enable(False)
    perf.enable(False)
    yield
    monitor.enable(was[0])
    trace.enable(was[1])
    perf.enable(was[2])


@pytest.mark.slow
@pytest.mark.parametrize("make", [_span, _phase, _counter_inc,
                                  _perf_segment, _inject_extract],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_disabled_call_under_a_microsecond(all_off, make):
    call = make()
    n, per_call = 50_000, float("inf")
    for _ in range(5):            # min-of-rounds: a loaded host must
        t0 = time.perf_counter()  # miss at least one window
        for i in range(n):
            call(i)
        per_call = min(per_call, (time.perf_counter() - t0) / n)
    assert per_call < 1e-6, (
        f"disabled {make.__name__.lstrip('_')} costs "
        f"{per_call * 1e9:.0f} ns a call")
