"""monitor.perf — MFU/roofline perf attribution (ISSUE 6 tentpole).

Covers the PTPU_PERF gate (a disabled segment is the shared no-op
object and records nothing; its per-call time is bounded by
tests/test_disabled_overhead.py), cost-analysis normalization (non-scalar entries
counted, never silently dropped — the CostModel bug the module dedupes
away), graceful degradation on stat-less backends (every derived figure
reads None/'unavailable', never garbage MFU), the jit CompiledFunction
perf hook + memory_analysis signature cache, the segment timers, the
`measure()` backend shared by CostModel.profile_measure, and the report
table.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import perf


@pytest.fixture(autouse=True)
def _fresh_perf():
    monitor.reset()
    monitor.enable(True)
    perf.reset()
    yield
    perf.enable(False)
    perf.reset()
    perf.refresh()
    monitor.reset()
    monitor.refresh()


# -- gate / overhead --------------------------------------------------------

def test_disabled_overhead_guard():
    """A disabled segment is the shared no-op object: no allocation, no
    clock read, nothing recorded.  The per-call time bound lives in
    tests/test_disabled_overhead.py (slow tier)."""
    perf.enable(False)
    seg = perf.segment("t", "x")
    assert seg is perf._NOOP_SEGMENT
    with seg as s:
        assert s.sync(np.zeros(2)) is perf._NOOP_SEGMENT
    assert perf.get("t:x") is None      # and records nothing
    assert perf.records() == []


def test_enable_refresh_roundtrip(monkeypatch):
    perf.enable(True)
    assert perf.enabled()
    perf.enable(False)
    assert not perf.enabled()
    monkeypatch.setenv("PTPU_PERF", "1")
    perf.refresh()
    assert perf.enabled()
    monkeypatch.setenv("PTPU_PERF", "0")
    perf.refresh()
    assert not perf.enabled()


# -- normalization / degradation --------------------------------------------

def test_normalize_cost_analysis_shapes():
    # jax versions return a dict, a 1-list of dicts, or None
    cost, dropped = perf.normalize_cost_analysis({"flops": 10, "bytes accessed": 4.0})
    assert cost == {"flops": 10.0, "bytes accessed": 4.0} and dropped == 0
    cost, dropped = perf.normalize_cost_analysis([{"flops": 10}])
    assert cost == {"flops": 10.0} and dropped == 0
    assert perf.normalize_cost_analysis(None) == ({}, 0)
    assert perf.normalize_cost_analysis([]) == ({}, 0)
    assert perf.normalize_cost_analysis("garbage") == ({}, 0)


def test_normalize_counts_dropped_non_scalars():
    cost, dropped = perf.normalize_cost_analysis(
        {"flops": 1.0, "utilization": {"mxu": 0.4}, "flag": True,
         "list": [1, 2]})
    assert cost == {"flops": 1.0}
    assert dropped == 3                 # dict + bool + list, all counted


def test_empty_analysis_reports_unavailable_not_garbage():
    perf.enable(True)
    rec = perf.capture("deg:empty", cost={}, memory=None)
    perf.observe("deg:empty", 0.01)
    assert not rec.available
    d = rec.as_dict()
    for k in ("flops", "bytes_accessed", "intensity", "mfu", "optimal_s",
              "achieved_vs_optimal", "peak_bytes", "hbm_headroom"):
        assert d[k] is None, (k, d[k])
    assert d["bound"] == perf.UNAVAILABLE
    assert d["calls"] == 1 and d["wall_best_s"] == 0.01
    # the table renders the row as unavailable instead of fabricating MFU
    table = perf.report()
    assert "deg:empty" in table and "unavailable" in table
    # the unavailability marker is exported; mfu/flops gauges are NOT
    snap = monitor.snapshot()
    assert snap["perf/analysis_unavailable"]["fn=deg:empty"] == 1.0
    mfu = snap.get("perf/mfu")
    assert not (isinstance(mfu, dict) and "fn=deg:empty" in mfu), mfu
    flops = snap.get("perf/flops")
    assert not (isinstance(flops, dict) and "fn=deg:empty" in flops), flops


def test_unavailable_marker_cleared_on_later_success():
    # a failed first capture flags the fn; a later successful capture for
    # the same label must clear the marker — /metrics must never report a
    # fn as simultaneously unavailable and fully analyzed
    perf.enable(True)
    perf.capture("deg:flaky", cost={})
    assert monitor.snapshot()["perf/analysis_unavailable"][
        "fn=deg:flaky"] == 1.0
    rec = perf.capture("deg:flaky", cost={"flops": 1e9,
                                          "bytes accessed": 1e8})
    assert rec.label == "deg:flaky" and rec.available
    snap = monitor.snapshot()
    assert snap["perf/analysis_unavailable"]["fn=deg:flaky"] == 0.0
    assert snap["perf/flops"]["fn=deg:flaky"] == 1e9


def test_achieved_vs_optimal_clamped_at_one():
    # a stand-in chip spec (CPU hosts) can under-state the real peaks,
    # putting the measured wall BELOW the "optimal" time; the documented
    # (0, 1] contract clamps instead of reporting faster-than-roofline
    perf.enable(True)
    rec = perf.capture("deg:fastwall", cost={"flops": 1e12,
                                             "bytes accessed": 1e9})
    perf.observe("deg:fastwall", 1e-6)      # far under optimal_s
    assert rec.optimal_s() > 1e-6
    assert rec.achieved_vs_optimal() == 1.0


def test_partial_analysis_flops_without_bytes():
    perf.enable(True)
    rec = perf.capture("deg:partial", cost={"flops": 1e9})
    perf.observe("deg:partial", 0.5)
    assert rec.available
    assert rec.intensity is None and rec.bound() == perf.UNAVAILABLE
    assert rec.optimal_s() is not None          # compute bound only
    assert rec.mfu() == pytest.approx(
        1e9 / 0.5 / perf.chip_spec().peak_flops)
    assert rec.hbm_headroom() is None           # no memory analysis
    assert "deg:partial" in perf.report()


def test_zero_flop_memory_only_program_still_ranks():
    # pure copy/scatter programs (a paged cache update) legitimately
    # report flops=0 with nonzero bytes: they are memory-roofline-only,
    # NOT unavailable — they must stay in the worst-segment ranking
    perf.enable(True)
    rec = perf.capture("deg:copyonly", cost={"flops": 0.0,
                                             "bytes accessed": 1e9})
    perf.observe("deg:copyonly", 0.5)
    assert rec.available
    assert rec.intensity == 0.0 and rec.bound() == "memory"
    assert rec.optimal_s() == pytest.approx(1e9 / perf.chip_spec().hbm_bw)
    assert rec.achieved_vs_optimal() == pytest.approx(
        rec.optimal_s() / 0.5)
    assert rec.mfu() is None        # MFU is a flops figure; no fiction
    table = perf.report()
    assert "deg:copyonly" in table
    line = next(ln for ln in table.splitlines() if "deg:copyonly" in ln)
    assert "unavailable" not in line and "memory" in line


def test_capture_from_raising_analysis_objects():
    class Broken:
        def cost_analysis(self):
            raise RuntimeError("no stats on this backend")

        def memory_analysis(self):
            raise RuntimeError("no stats on this backend")

    perf.enable(True)
    rec = perf.capture("deg:raises", lowered=Broken(), compiled=Broken())
    assert not rec.available and rec.memory == {}
    snap = monitor.snapshot()
    errs = [k for k in snap if k.startswith("perf/capture_errors")]
    assert errs, sorted(snap)


def test_memory_dict_from_stats_object():
    class Stats:
        argument_size_in_bytes = 100
        output_size_in_bytes = 10
        temp_size_in_bytes = 50
        alias_size_in_bytes = 20
        generated_code_size_in_bytes = 1

    rec = perf.capture("mem:obj", memory=Stats())
    assert rec.memory["peak_bytes_estimate"] == 100 + 50 - 20
    assert rec.hbm_headroom() == pytest.approx(
        perf.chip_spec().hbm_bytes / 130)


def test_chip_spec_env_overrides(monkeypatch):
    monkeypatch.setenv("PTPU_PERF_PEAK_FLOPS", "100e12")
    monkeypatch.setenv("PTPU_PERF_HBM_GBS", "1000")
    monkeypatch.setenv("PTPU_PERF_HBM_GIB", "32")
    chip = perf.chip_spec(refresh_probe=True)
    try:
        assert chip.peak_flops == 100e12
        assert chip.hbm_bw == 1000e9
        assert chip.hbm_bytes == 32 * 2**30
        assert chip.ridge == pytest.approx(100.0)
    finally:
        monkeypatch.delenv("PTPU_PERF_PEAK_FLOPS")
        monkeypatch.delenv("PTPU_PERF_HBM_GBS")
        monkeypatch.delenv("PTPU_PERF_HBM_GIB")
        perf.chip_spec(refresh_probe=True)


# -- segments ---------------------------------------------------------------

def test_segment_records_and_exports():
    perf.enable(True)
    with perf.segment("seg", "alpha") as s:
        x = paddle.to_tensor(np.ones((4, 4), np.float32)) * 2
        s.sync(x)
    rec = perf.get("seg:alpha")
    assert rec is not None and rec.calls == 1 and rec.best_s > 0
    snap = monitor.snapshot()
    h = snap["perf/segment_time"]["segment=alpha,step=seg"]
    assert h["count"] == 1, h


def test_observe_segment_merges_into_records():
    perf.enable(True)
    perf.observe_segment("seg", "beta", 0.25)
    perf.observe_segment("seg", "beta", 0.125)
    rec = perf.get("seg:beta")
    assert rec.calls == 2 and rec.best_s == 0.125


# -- measure / jit hook -----------------------------------------------------

def test_measure_small_program():
    import jax.numpy as jnp

    perf.enable(True)

    def fn(a, b):
        return (a @ b).sum()

    a = jnp.ones((64, 64), jnp.float32)
    res = perf.measure(fn, a, a, label="meas:mm", reps=2)
    assert res["wall_time_s"] > 0 and res["calls"] >= 1
    # XLA-CPU provides cost analysis: the roofline fields must be real
    if res["available"]:
        assert res["flops"] > 0
        assert res["bound"] in ("compute", "memory")
        assert 0 < res["achieved_vs_optimal"] <= 1.0
        assert res["mfu"] is not None
    assert "meas:mm" in perf.report()


def test_cost_model_dedupes_onto_measure():
    from paddle_tpu.cost_model import CostModel

    res = CostModel().profile_measure(
        lambda t: t @ t, paddle.to_tensor(np.ones((32, 32), np.float32)))
    assert res["wall_time_s"] > 0
    # prior callers' contract: raw scalar analysis keys at the top level
    if res["available"]:
        assert res["flops"] > 0
        assert res["bound"] in ("compute", "memory")
    else:
        assert res["mfu"] is None


def test_jit_hook_captures_and_memory_analysis_cached():
    from paddle_tpu import jit, nn

    perf.enable(True)
    layer = nn.Linear(16, 16)

    def step(x):
        return layer(x).sum()

    c = jit.compile(step, models=[layer], train=False)
    x = paddle.to_tensor(np.ones((4, 16), np.float32))
    for _ in range(3):
        c(x)
    rec = perf.get("step")
    assert rec is not None and rec.calls == 3
    if rec.available:
        assert rec.flops > 0
    # memory_analysis: first call fills the signature cache, repeats are
    # answered from it (no re-lower/re-compile)
    ma1 = c.memory_analysis(x)
    assert ma1["peak_bytes_estimate"] >= 0
    assert c._analysis_cache
    calls = {"n": 0}
    orig = c.lower

    def counting_lower(*a, **k):
        calls["n"] += 1
        return orig(*a, **k)

    c.lower = counting_lower
    assert c.memory_analysis(x) == ma1
    assert calls["n"] == 0, "repeat memory_analysis re-lowered"


def test_jit_perf_off_no_records():
    from paddle_tpu import jit, nn

    perf.enable(False)
    layer = nn.Linear(8, 8)
    c = jit.compile(lambda x: layer(x).sum(), models=[layer], train=False)
    c(paddle.to_tensor(np.ones((2, 8), np.float32)))
    assert perf.records() == []


# -- report -----------------------------------------------------------------

def test_report_ranks_and_names_worst():
    perf.enable(True)
    perf.capture("rank:good", cost={"flops": 1e9, "bytes accessed": 1e6})
    perf.observe("rank:good", 1e9 / perf.chip_spec().peak_flops * 2)  # 0.5
    perf.capture("rank:bad", cost={"flops": 1e9, "bytes accessed": 1e6})
    perf.observe("rank:bad", 1e9 / perf.chip_spec().peak_flops * 100)
    table = perf.report()
    assert "worst achieved-vs-optimal: rank:bad" in table
    # merged into Profiler.summary()
    from paddle_tpu import profiler

    with profiler.Profiler(timer_only=True) as prof:
        prof.step()
    assert "perf attribution" in prof.summary()


def test_report_empty_when_nothing_recorded():
    assert perf.report() == ""

