"""Auto-parallel plan search (reference: auto_parallel/tuner/
optimization_tuner.py:196 OptimizationTuner + auto_parallel/cost/ —
profile-or-estimate candidate parallel strategies and pick the best).

TPU-native re-design: GSPMD already does sharding PROPAGATION (the
reference Completer/Partitioner/Resharder, SURVEY §2.5); what remains is
the SEARCH over mesh shapes. The tuner enumerates factorizations of the
chip count over the hybrid axes (dp, sharding, pp, mp), scores each with
an analytical roofline model of one training step — MXU compute at a
target MFU, ICI collective time per axis, pipeline bubble, HBM footprint
— and returns plans ranked by estimated step time with infeasible
(out-of-memory, indivisible) plans pruned. `measure=True` optionally
refines the top candidates by compiling + running them on the current
(virtual or real) mesh, the analog of the reference tuner's trial runs.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import time
from typing import List, Optional

__all__ = ["ClusterSpec", "ModelSpec", "Plan", "OptimizationTuner",
           "DEFAULT_CALIBRATION_PATH"]

# On-target calibration artifact (written by scripts/tuner_calibrate_tpu.py
# on a chip; committed so every later session's estimates are grounded in
# measured hardware ratios rather than the analytic roofline alone —
# reference: tuner/profiler.py profiles candidate configs on the actual
# device).
DEFAULT_CALIBRATION_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "calibration", "tuner_tpu.json")


@dataclasses.dataclass
class ClusterSpec:
    """Hardware model (defaults: one v5e pod slice)."""
    n_devices: int = 8
    hbm_bytes: float = 16e9
    peak_flops: float = 197e12          # bf16 MXU
    ici_bandwidth: float = 9e10         # per-device all-reduce effective B/s
    dcn_bandwidth: float = 2.5e10       # across-host axis (dp outermost)
    target_mfu: float = 0.4


@dataclasses.dataclass
class ModelSpec:
    """Transformer-shaped workload (the reference tuner is likewise
    transformer-centric: dist_matmul + embedding + attention patterns)."""
    n_params: int
    n_layers: int
    hidden: int
    seq_len: int
    global_batch: int
    vocab: int = 50304
    heads: int = 0
    dtype_bytes: int = 2                # bf16 params/activations
    optimizer_state_bytes: int = 12     # fp32 master + moments per param

    @classmethod
    def from_gpt_config(cls, cfg, global_batch):
        H, L, V = cfg.hidden_size, cfg.num_hidden_layers, cfg.vocab_size
        I = cfg.intermediate_size
        n = V * H + cfg.max_position_embeddings * H + L * (
            4 * H * H + 2 * H * I + 9 * H) + 2 * H
        return cls(n_params=int(n), n_layers=L, hidden=H,
                   seq_len=cfg.max_position_embeddings,
                   global_batch=global_batch, vocab=V,
                   heads=cfg.num_attention_heads)


@dataclasses.dataclass
class Plan:
    dp: int = 1
    sharding: int = 1
    pp: int = 1
    mp: int = 1
    sp: int = 1                  # context parallel (ring attention)
    microbatches: int = 1
    recompute: bool = True       # per-block activation remat
    est_step_time: float = float("inf")
    est_memory: float = float("inf")
    breakdown: dict = dataclasses.field(default_factory=dict)
    feasible: bool = True
    reason: str = ""

    def mesh_kwargs(self):
        return dict(dp=self.dp, sharding=self.sharding, pp=self.pp,
                    mp=self.mp, sp=self.sp)


def _divisors(n):
    return [d for d in range(1, n + 1) if n % d == 0]


class OptimizationTuner:
    def __init__(self, model: ModelSpec, cluster: Optional[ClusterSpec] = None):
        self.model = model
        self.cluster = cluster or ClusterSpec()
        # measured/estimated ratios fitted from trial runs
        # (tune(measure=True)); 1.0 = uncalibrated analytic roofline.
        # calibration: global median (reporting/back-compat);
        # calib_compute/calib_comm: split factors — a single global factor
        # rescales every estimate identically and can never change the
        # RANKING, so re-ranking power comes from calibrating the compute
        # and communication terms separately.
        self.calibration = 1.0
        self.calib_compute = 1.0
        self.calib_comm = 1.0
        self.comm_fitted = False   # True only when comm-heavy trials
        #                            independently pinned calib_comm
        self.last_report: Optional[dict] = None

    # -- analytical roofline -------------------------------------------------
    def estimate(self, plan: Plan) -> Plan:
        m, c = self.model, self.cluster
        dp, sh, pp, mp, sp = (plan.dp, plan.sharding, plan.pp, plan.mp,
                              plan.sp)
        M = plan.microbatches
        n_dev = dp * sh * pp * mp * sp

        # divisibility pruning
        if n_dev != c.n_devices:
            return dataclasses.replace(plan, feasible=False,
                                       reason="device count mismatch")
        if m.n_layers % pp:
            return dataclasses.replace(plan, feasible=False,
                                       reason=f"layers {m.n_layers} % pp")
        if m.hidden % mp or (m.heads and m.heads % mp):
            return dataclasses.replace(plan, feasible=False,
                                       reason="hidden/heads % mp")
        if sp > 1 and (m.seq_len % (2 * sp) or pp > 1):
            # ring attention shards the sequence (zigzag wants 2*sp
            # divisibility); it does not compose with pp stages
            return dataclasses.replace(plan, feasible=False,
                                       reason="seq % 2*sp or sp with pp")
        repl = dp * sh  # data-consuming ways
        if m.global_batch % (repl * M):
            return dataclasses.replace(plan, feasible=False,
                                       reason="batch % (dp*sharding*microbatches)")

        tokens = m.global_batch * m.seq_len
        P = m.n_params
        B = m.dtype_bytes

        # compute: 6N dense + attention quadratic term, fwd+bwd; remat
        # re-runs the forward inside the backward (8N instead of 6N)
        dense = (8.0 if plan.recompute else 6.0) * P * tokens
        attn_q = ((16.0 if plan.recompute else 12.0)
                  * m.n_layers * m.seq_len * m.hidden * tokens)
        flops = dense + attn_q
        t_comp = flops / (n_dev * c.peak_flops * c.target_mfu)

        # per-device parameter shard (mp and pp partition the weights;
        # ZeRO 'sharding' partitions the UPDATE/state, grads still reduce)
        p_shard = P / (pp * mp)

        # dp/sharding axis: grad reduction, 2(k-1)/k * bytes / bw; dp rides
        # DCN when it is the outermost multi-host axis, sharding rides ICI
        t_dp = 0.0
        if dp > 1:
            bw = c.dcn_bandwidth if n_dev > 8 else c.ici_bandwidth
            t_dp = 2 * (dp - 1) / dp * p_shard * B / bw
        if sh > 1:
            # reduce-scatter grads + all-gather updated params
            t_dp += 2 * (sh - 1) / sh * p_shard * B / c.ici_bandwidth
        if sp > 1:
            # sp ranks hold FULL weight grads (only the sequence is
            # sharded), so gradients also all-reduce across sp
            t_dp += 2 * (sp - 1) / sp * p_shard * B / c.ici_bandwidth
        t_dp *= 0.3  # most of it overlaps the backward (XLA LHS)

        # mp axis: 4 activation all-reduces per layer (2 fwd + 2 bwd),
        # activation tensor is the per-device micro-batch slice
        t_mp = 0.0
        act_loc = (m.global_batch / repl / M) * (m.seq_len / sp) \
            * m.hidden * B
        if mp > 1:
            t_mp = (m.n_layers / pp) * 4 * 2 * (mp - 1) / mp * act_loc \
                / c.ici_bandwidth * M
        if sp > 1:
            # ring attention: per layer the local K and V shards make
            # (sp-1) ICI hops each (fwd + bwd ~2x). The hopped shards are
            # heads/mp wide — unlike the mp all-reduce (full hidden), the
            # ring moves only this device's K/V slice
            t_mp += (m.n_layers / pp) * 2 * 2 * (sp - 1) * (act_loc / mp) \
                / c.ici_bandwidth * M

        # pp bubble stretches the whole step
        bubble = (pp - 1) / (M + pp - 1) if pp > 1 else 0.0
        step = (self.calib_compute * t_comp + self.calib_comm * t_mp) \
            / (1 - bubble) + self.calib_comm * t_dp

        # memory: params + grads (bf16) over pp*mp; optimizer state
        # additionally over 'sharding' (ZeRO); activations (seq sharded
        # over sp; ~6 live tensors/layer with remat, ~14 without);
        # 1F1B keeps <= pp micro-batches in flight
        mem = p_shard * B                      # params
        mem += p_shard * B                     # grads
        mem += p_shard * m.optimizer_state_bytes / sh
        act_layer = act_loc * (6 if plan.recompute else 14)
        live_mb = min(pp, M) if pp > 1 else 1
        mem += act_layer * (m.n_layers / pp) * live_mb / mp
        mem += (m.global_batch / repl / M) * (m.seq_len / sp) \
            * m.vocab * B / mp

        feasible = mem <= 0.9 * c.hbm_bytes
        return dataclasses.replace(
            plan, est_step_time=step, est_memory=mem, feasible=feasible,
            reason="" if feasible else "exceeds HBM",
            breakdown=dict(t_compute=t_comp, t_grad_comm=t_dp,
                           t_mp_comm=t_mp, pp_bubble=bubble))

    # -- search --------------------------------------------------------------
    def candidates(self) -> List[Plan]:
        n = self.cluster.n_devices
        out = []
        for mp in _divisors(n):
            for pp in _divisors(n // mp):
                for sp in _divisors(n // (mp * pp)):
                    if sp > 1 and (pp > 1
                                   or self.model.seq_len % (2 * sp)):
                        continue   # pruned in estimate anyway; skip early
                    for sh in _divisors(n // (mp * pp * sp)):
                        dp = n // (mp * pp * sp * sh)
                        # sorted: set order is PYTHONHASHSEED-dependent
                        # and this feeds Plan enumeration order (tie-break
                        # selection must be stable across processes)
                        for mb in sorted({1, pp, 2 * pp, 4 * pp} - {0}):
                            for rc in (True, False):
                                out.append(Plan(
                                    dp=dp, sharding=sh, pp=pp, mp=mp,
                                    sp=sp, microbatches=max(1, mb),
                                    recompute=rc))
        return out

    def tune(self, top_k: int = 5, measure: bool = False,
             measure_top_k: int = 8, report_path: Optional[str] = None
             ) -> List[Plan]:
        """Rank candidate plans; with measure=True run a short compiled
        trial for the top `measure_top_k` candidates on the current
        (virtual or real) mesh, calibrate the roofline from the trials,
        and choose by MEASUREMENT (reference: tuner/optimization_tuner.py
        profile mode + tuner/profiler.py). A JSON tuning report is stored
        on self.last_report (and written to report_path when given)."""
        plans = [self.estimate(p) for p in self.candidates()]
        ranked = sorted((p for p in plans if p.feasible),
                        key=lambda p: p.est_step_time)
        trials: List[Plan] = []
        if measure and ranked:
            trials = self._measure(ranked[:max(measure_top_k, top_k)])
            self._fit_calibration(trials)
            # measured plans rank by wall clock; unmeasured keep their
            # (calibrated) estimates behind every measured one
            def key(p):
                m = p.breakdown.get("measured_s")
                return (0, m) if m else (1, p.est_step_time * self.calibration)
            ranked = sorted(trials, key=key) + ranked[len(trials):]
        self.last_report = {
            "model": dataclasses.asdict(self.model),
            "cluster": dataclasses.asdict(self.cluster),
            "n_candidates": len(plans),
            "n_feasible": sum(p.feasible for p in plans),
            "calibration": self.calibration,
            "trials": [dataclasses.asdict(p) for p in trials],
            "chosen": dataclasses.asdict(ranked[0]) if ranked else None,
            "ranked": [dataclasses.asdict(p) for p in ranked[:top_k]],
        }
        if report_path:
            import json

            with open(report_path, "w") as f:
                json.dump(self.last_report, f, indent=1)
        return ranked[:top_k]

    def _fit_calibration(self, trials: List[Plan]) -> None:
        """Fit (calib_compute, calib_comm) from trial runs: trials whose
        estimated comm share is small pin the compute factor; comm-heavy
        trials then pin the comm factor given that fit. The global median
        ratio is kept for reporting. When only one term is separable
        (single-chip trial sets), BOTH factors degrade to the global
        ratio — magnitude calibrated, analytic ranking preserved — and
        comm_fitted stays False so the artifact records that the comm
        factor is not a measured fit."""
        pts = []
        for p in trials:
            ms = p.breakdown.get("measured_s")
            te = p.breakdown.get("trial_est_s")
            tb = p.breakdown.get("trial_breakdown")
            if not ms or not te or not tb:
                continue
            bubble = tb.get("pp_bubble", 0.0)
            comp = tb.get("t_compute", 0.0) / max(1 - bubble, 1e-9)
            comm = max(te - comp, 0.0)
            pts.append((ms, comp, comm))
        if not pts:
            return
        ratios = sorted(ms / (c + m) for ms, c, m in pts if c + m > 0)
        if ratios:
            self.calibration = ratios[len(ratios) // 2]
        comp_pts = [x for x in pts if x[2] <= 0.2 * (x[1] + x[2])]
        comm_pts = [x for x in pts if x[2] > 0.2 * (x[1] + x[2])]
        fit_comp = fit_comm = None
        if comp_pts:
            rs = sorted(ms / c for ms, c, _ in comp_pts if c > 0)
            if rs:
                fit_comp = rs[len(rs) // 2]
        if comm_pts:
            rs = sorted((ms - (fit_comp or 1.0) * c) / m
                        for ms, c, m in comm_pts if m > 0)
            rs = [r for r in rs if r > 0]
            if rs:
                fit_comm = rs[len(rs) // 2]
        if fit_comp is not None and fit_comm is not None:
            self.calib_compute, self.calib_comm = fit_comp, fit_comm
            self.comm_fitted = True
        else:
            # only one term separable (e.g. every trial comm-heavy, or a
            # single-chip trial set): a lone split factor DISTORTS the
            # ranking (observed: a CPU-mesh fit pushed calib_comm to ~3e5
            # while compute stayed 1.0, re-ranking garbage); degrade to
            # the uniform global ratio, which calibrates magnitude and
            # preserves the analytic ranking
            self.calib_compute = self.calib_comm = self.calibration

    # -- on-target calibration persistence -----------------------------------
    def save_calibration(self, path: str = None) -> str:
        """Persist the measured/estimated ratio (plus the cluster model it
        was fitted against and the platform it was measured on) so later
        sessions can ground their estimates without re-measuring."""
        path = path or DEFAULT_CALIBRATION_PATH
        if os.path.dirname(path):
            os.makedirs(os.path.dirname(path), exist_ok=True)
        platform = "unknown"
        try:
            import jax

            platform = jax.devices()[0].platform
        except Exception:  # ptpu-check[silent-except]: platform tag on the calibration
            # payload is metadata only
            pass
        payload = {
            "calibration": self.calibration,
            "calib_compute": self.calib_compute,
            "calib_comm": self.calib_comm,
            "comm_fitted": self.comm_fitted,
            "platform": platform,
            "cluster": dataclasses.asdict(self.cluster),
            "fitted_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
            "model": dataclasses.asdict(self.model),
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=1)
        return path

    def load_calibration(self, path: str = None,
                         require_platform: str = None) -> bool:
        """Apply a persisted calibration. Returns False (leaving the
        analytic 1.0) when the file is absent or was fitted on a different
        platform than `require_platform`."""
        path = path or DEFAULT_CALIBRATION_PATH
        try:
            with open(path) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            return False
        if (require_platform is not None
                and payload.get("platform") != require_platform):
            return False
        self.calibration = float(payload["calibration"])
        # both split keys default to the GLOBAL ratio: mixing a calibrated
        # compute factor with an uncalibrated comm one is exactly the
        # lone-split-factor distortion _fit_calibration degrades to avoid
        self.calib_compute = float(payload.get("calib_compute",
                                               payload["calibration"]))
        self.calib_comm = float(payload.get("calib_comm",
                                            payload["calibration"]))
        self.comm_fitted = bool(payload.get("comm_fitted", False))
        return True

    def best(self) -> Plan:
        ranked = self.tune(top_k=1)
        if not ranked:
            raise RuntimeError(
                "no feasible parallel plan for this model on "
                f"{self.cluster.n_devices} devices — more chips or a "
                "smaller per-device footprint (sharding/pp) is required")
        return ranked[0]

    def _measure(self, plans: List[Plan]) -> List[Plan]:
        """Trial-run refinement (reference tuner's profile mode): time one
        tiny compiled step per plan on the available mesh."""
        import time

        import jax
        import numpy as np

        from ..optimizer import AdamW
        from .. import jit as _jit
        from ..models import GPTForCausalLM, GPTPretrainingCriterion, gpt_test_config
        from ..parallel import init_mesh, place_model, get_mesh
        from ..parallel.mesh import set_mesh

        prior_mesh = get_mesh()  # restored after trials — tune() must not
        measured = []            # leave the user's mesh on a trial config
        for plan in plans:
            if (plan.dp * plan.sharding * plan.pp * plan.mp * plan.sp
                    > len(jax.devices())):
                measured.append(plan)
                continue
            try:
                init_mesh(**plan.mesh_kwargs())
                cfg = gpt_test_config(
                    num_hidden_layers=max(2, plan.pp), stacked_blocks=True,
                    pp_num_microbatches=plan.microbatches,
                    context_parallel=plan.sp > 1,
                    recompute=plan.recompute)
                model = place_model(GPTForCausalLM(cfg))
                crit = GPTPretrainingCriterion(cfg)
                opt = AdamW(learning_rate=1e-4, parameters=model.parameters())

                def step(x, y):
                    loss = crit(model(x), y)
                    loss.backward()
                    opt.step()
                    opt.clear_grad()
                    return loss

                compiled = _jit.compile(step, models=[model], optimizers=[opt])
                rng = np.random.RandomState(0)
                B = max(plan.dp * plan.sharding * plan.microbatches, 4)
                from ..core.tensor import Tensor
                import jax.numpy as jnp
                ids = Tensor(jnp.asarray(rng.randint(0, 128, (B, 16)), jnp.int32))
                lab = Tensor(jnp.asarray(rng.randint(0, 128, (B, 16)), jnp.int32))
                compiled(ids, lab)
                t0 = time.perf_counter()
                for _ in range(3):
                    out = compiled(ids, lab)
                float(out)
                wall = (time.perf_counter() - t0) / 3
                # roofline estimate of the TRIAL workload itself: the
                # measured/estimated ratio calibrates the model constants
                # for the mesh actually measured on
                trial_spec = ModelSpec.from_gpt_config(cfg, B)
                trial_spec = dataclasses.replace(trial_spec, seq_len=16)
                trial_est = OptimizationTuner(trial_spec, self.cluster).estimate(
                    dataclasses.replace(plan, breakdown={}))
                measured.append(dataclasses.replace(
                    plan, breakdown=dict(
                        plan.breakdown, measured_s=wall,
                        trial_est_s=(trial_est.est_step_time
                                     if trial_est.est_step_time < float("inf")
                                     else None),
                        trial_breakdown=trial_est.breakdown)))
            except Exception as e:  # infeasible at runtime: keep estimate
                measured.append(dataclasses.replace(
                    plan, breakdown=dict(plan.breakdown,
                                         measure_error=str(e)[:200])))
        set_mesh(prior_mesh)
        return measured
