"""Ground-truth throughput oracles standing in for the 64-GPU A800 cluster.

The paper measures real runs; this repro is CPU-only, so the "real cluster"
is an oracle with the SAME structural equations but hidden, per-model true
parameters plus plan-conditioned efficiency wiggles and measurement noise —
the scheduler's fitted model never sees the truth, so Table-2-style
prediction errors are earned, not circular.
"""

from __future__ import annotations

import hashlib
import math
from dataclasses import dataclass

import numpy as np

from repro.core import memory
from repro.core.perfmodel import (_BOUNDS, Alloc, Env, FitParams,
                                  ModelProfile, predict_titer,
                                  predict_titer_batch)
from repro.parallel.plan import ExecutionPlan
from repro.parallel.plan_table import PlanTable


def _unit_hash(*keys) -> float:
    h = hashlib.sha256("|".join(str(k) for k in keys).encode()).digest()
    return int.from_bytes(h[:8], "big") / 2**64


def true_params(model_name: str) -> FitParams:
    """Deterministic hidden truth per model type."""
    u = lambda key, lo, hi: lo + (hi - lo) * _unit_hash(model_name, key)
    return FitParams(
        k_bwd=u("bwd", 1.7, 2.4),
        k_sync=u("sync", 1.5, 8.0),
        k_opt=10 ** u("opt", -11.5, -10.5),
        # CPU-side Adam is slow enough to dominate PCIe transfer (the paper's
        # Fig 7 observation: doubling CPUs under ZeRO-Offload gives ~1.7×)
        k_opt_off=10 ** u("optoff", -9.4, -8.9),
        k_off=u("off", 1.5, 8.0),
        k_swap=u("swap", 1.5, 8.0),
        k_const=u("const", 0.002, 0.05),
    )


@dataclass
class AnalyticOracle:
    """measure(profile, plan, alloc) -> T_iter seconds (or inf if OOM).

    ``drifting=True`` slowly perturbs the hidden true params over
    SIMULATED time (``now``): each of the 7 params follows its own
    deterministic log-space direction, saturating at
    ``exp(±drift_scale)`` with time constant ``drift_tau`` — so a model
    fitted from the t=0 profile grows stale, and online calibration has
    something real to catch.  The drifted truth is clamped to
    ``perfmodel._BOUNDS`` so a refit can always reach it (tanh
    saturation alone is not enough: a hash draw near a bound edge with
    an outward drift direction would escape)."""
    env: Env = None
    noise: float = 0.01
    wiggle: float = 0.06          # plan-family efficiency deviation
    drifting: bool = False
    drift_scale: float = 0.6      # log-space drift amplitude at saturation
    drift_tau: float = 43200.0    # drift time constant, seconds (12 h)

    def __post_init__(self):
        self.env = self.env or Env()

    def true_params_at(self, model_name: str, now: float = 0.0) -> FitParams:
        """Hidden truth at simulated time ``now`` (= ``true_params`` at
        t=0 or when drifting is off)."""
        k = true_params(model_name)
        if not self.drifting or now <= 0.0:
            return k
        v = k.as_vector()
        dirs = np.array([2.0 * _unit_hash(model_name, "drift", i) - 1.0
                         for i in range(v.size)])
        v = v * np.exp(self.drift_scale * dirs * math.tanh(now /
                                                           self.drift_tau))
        v = np.clip(v, [b[0] for b in _BOUNDS], [b[1] for b in _BOUNDS])
        return FitParams.from_vector(v)

    def measure(self, profile: ModelProfile, plan: ExecutionPlan,
                alloc: Alloc, seed: int = 0,
                env: Env | None = None, now: float = 0.0) -> float:
        """``env`` overrides the oracle's default environment — the
        simulator passes the per-GPU-type Env of the nodes actually
        hosting the job on heterogeneous clusters.  ``now`` selects the
        drifted truth on drifting oracles (ignored otherwise)."""
        env = env or self.env
        if not memory.feasible(profile, plan, alloc, env):
            return float("inf")
        k = self.true_params_at(profile.name, now)
        t = predict_titer(profile, plan, alloc, env, k)
        if not math.isfinite(t):
            return float("inf")
        # plan-family wiggle: the truth is not exactly the model's form
        w = 1.0 + self.wiggle * (2 * _unit_hash(
            profile.name, plan.strategy, alloc.gpus) - 1)
        rng = np.random.default_rng(
            int(_unit_hash(profile.name, plan, alloc, seed) * 2**31))
        noise = float(rng.lognormal(0.0, self.noise))
        return t * w * noise

    def throughput(self, profile, plan, alloc, seed: int = 0,
                   env: Env | None = None, now: float = 0.0) -> float:
        t = self.measure(profile, plan, alloc, seed, env=env, now=now)
        return profile.b / t if math.isfinite(t) and t > 0 else 0.0

    # ------------------------------------------------------------------
    def measure_batch(self, profile: ModelProfile, table: PlanTable,
                      gpus: int, cpus: int, seed: int = 0) -> np.ndarray:
        """T_iter for every table row at one allocation (inf where OOM) —
        vectorized core prediction; the per-row wiggle/noise hashing stays
        scalar (cheap) so values match ``measure`` row-for-row."""
        g = np.asarray([gpus])
        c = np.asarray([float(cpus)])
        cols = table.cols.expand()
        feas = memory.feasible_mask(profile, cols, g, c, self.env)[:, 0]
        t = predict_titer_batch(profile, cols, g, c, self.env,
                                true_params(profile.name))[:, 0]
        out = np.full(len(table), np.inf)
        alloc = Alloc(gpus, cpus)
        for i in np.flatnonzero(feas & np.isfinite(t)):
            w = 1.0 + self.wiggle * (2 * _unit_hash(
                profile.name, table.strategies[i], alloc.gpus) - 1)
            rng = np.random.default_rng(int(_unit_hash(
                profile.name, table.plans[i], alloc, seed) * 2**31))
            out[i] = t[i] * w * float(rng.lognormal(0.0, self.noise))
        return out

    def throughput_batch(self, profile: ModelProfile, table: PlanTable,
                         gpus: int, cpus: int, seed: int = 0) -> np.ndarray:
        t = self.measure_batch(profile, table, gpus, cpus, seed)
        ok = np.isfinite(t) & (t > 0)
        return np.where(ok, profile.b / np.where(ok, t, 1.0), 0.0)


def true_curve(profile: ModelProfile, env: Env | None = None,
               max_gpus: int = 64, cpus_per_gpu: int = 12, max_ga: int = 8):
    """The GROUND-TRUTH sensitivity curve (hidden params, no wiggle/noise)
    — shares the process-wide CurveCache with the scheduler stack, so
    benchmarks comparing predicted vs true envelopes enumerate the plan
    space once."""
    from repro.core.sensitivity import get_curve
    return get_curve(profile, true_params(profile.name), env or Env(),
                     max_gpus=max_gpus, cpus_per_gpu=cpus_per_gpu,
                     max_ga=max_ga)


PROFILE_SET = "paper Sec 4.3: ≥7 points, ≥3 with ZeRO-Offload"


def profiling_samples(profile: ModelProfile, oracle: AnalyticOracle,
                      max_gpus: int = 8,
                      ) -> list[tuple[ExecutionPlan, Alloc, float]]:
    """The minimum profiling set (7 points, 3 with offload) the paper uses,
    restricted to plans feasible at ≤ max_gpus."""
    cands: list[tuple[ExecutionPlan, Alloc]] = []
    g_hi = max_gpus
    g_mid = max(2, max_gpus // 2)
    cpus = lambda g: 12 * g
    cands += [
        (ExecutionPlan(dp=g_hi, zero_stage=1), Alloc(g_hi, cpus(g_hi))),
        (ExecutionPlan(dp=g_mid, ga_steps=2), Alloc(g_mid, cpus(g_mid))),
        (ExecutionPlan(dp=g_hi, zero_stage=3, gc=True), Alloc(g_hi, cpus(g_hi))),
        (ExecutionPlan(dp=1, tp=min(4, g_mid)), Alloc(min(4, g_mid),
                                                      cpus(min(4, g_mid)))),
        (ExecutionPlan(dp=g_hi, zero_stage=1, offload=True),
         Alloc(g_hi, cpus(g_hi))),
        (ExecutionPlan(dp=g_mid, zero_stage=1, offload=True, ga_steps=2),
         Alloc(g_mid, cpus(g_mid))),
        (ExecutionPlan(dp=1, zero_stage=1, offload=True, gc=True),
         Alloc(1, 12)),
    ]
    out = []
    for plan, alloc in cands:
        if profile.b % (plan.dp * max(plan.ga_steps, 1)):
            continue
        t = oracle.measure(profile, plan, alloc)
        if math.isfinite(t):
            out.append((plan, alloc, t))
    return out


def profiling_requests(profiles, oracle: AnalyticOracle,
                       env: Env | None = None, max_gpus: int = 8):
    """Profile each model type and package the fit inputs for ONE
    ``repro.core.fitting.fit_batch`` call — the shared cold-start entry
    point (``Simulator`` pre-fits every cache-missed model type of a
    trace this way; ``benchmarks._artifacts`` pre-warms the Table-2
    cache the same way, so cache keys/values stay result-identical).

    Returns ``(requests, skipped)``: one ``FitRequest`` per profile with
    enough feasible profiling samples, and ``(profile, samples)`` for
    the rest (< 4 points — the project-wide fit floor; callers fall back
    to default ``FitParams`` and surface the type as uncalibrated — the
    collected samples ride along so no caller re-profiles)."""
    from repro.core.fitting import FitRequest
    env = env or oracle.env
    requests, skipped = [], []
    for profile in profiles:
        samples = profiling_samples(profile, oracle, max_gpus=max_gpus)
        if len(samples) >= 4:
            requests.append(FitRequest(profile=profile,
                                       samples=tuple(samples), env=env))
        else:
            skipped.append((profile, samples))
    return requests, skipped

