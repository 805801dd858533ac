"""SLO auto-tuner payoff: tuned sessions vs the static one-size default.

Builds a fast :class:`~repro.tuning.TuningProfile` on scaled-down probes
(the ``scripts/tune.py --fast`` path, in-process), then serves the same
drifting workload twice per domain: once with the static default
``SolveConfig()`` (k=4 for every tenant) and once through
``PopService(profile=...).session(..., slo=SLOTarget(0.02))``.  Reports
steady-state steps/sec and the *realized* quality (domain quality scalar
over a per-round reference full solve) for both — the headline is that
the measured-curve pick meets the 2% SLO while stepping faster than the
static default wherever the domain's curve allows a larger k (cluster
scheduling's flat curve) and holds quality where it does not (traffic's
steep curve).

    PYTHONPATH=src python -m benchmarks.bench_tuning [--fast]
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from repro.core import ExecConfig, SolveConfig, pop as pop_mod
from repro.domains import GavelInstance, registry as registry_mod
from repro.problems.cluster_scheduling import make_cluster_workload
from repro.problems.traffic_engineering import (TrafficProblem,
                                                k_shortest_paths,
                                                make_demands, make_topology)
from repro.service import PopService
from repro.tuning import SLOTarget, build_profile, profile_digest
from .common import emit, save_json, use_compile_cache

SLO = SLOTarget(max_quality_loss=0.02)


def _scenarios(fast: bool, rng):
    """(domain, first instance, drift fn) per benched domain."""
    kw = dict(max_iters=1_500 if fast else 4_000, tol_primal=1e-4,
              tol_gap=1e-4)
    n_jobs = 96 if fast else 256
    n_dem = 160 if fast else 600

    wl = make_cluster_workload(n_jobs, seed=3)
    ginst = GavelInstance(wl, job_ids=np.arange(n_jobs))

    def drift_gavel(inst, rng=rng):
        wl2 = dataclasses.replace(
            inst.wl, T=inst.wl.T * rng.uniform(0.95, 1.05, inst.wl.T.shape))
        return GavelInstance(wl2, job_ids=inst.job_ids)

    topo = make_topology(20, 40, seed=3)
    pairs, dem = make_demands(topo, n_dem, seed=3)
    pe = k_shortest_paths(topo, pairs, n_paths=2, max_len=10, seed=3)
    tinst = TrafficProblem(topo, pairs, dem, pe)

    def drift_traffic(inst, rng=rng):
        return TrafficProblem(
            inst.topo, inst.pairs,
            inst.demand * rng.uniform(0.97, 1.03, inst.demand.shape[0]),
            inst.path_edges)

    return [("gavel", ginst, drift_gavel, ExecConfig(solver_kw=kw)),
            ("traffic", tinst, drift_traffic, ExecConfig(solver_kw=kw))]


def _ref_quality(spec, inst, exec_cfg):
    """Per-round realized-quality reference: a CONVERGED k=1 full solve
    (the serving arms run capped budgets; the reference must not)."""
    kw = dict(exec_cfg.solver_dict())
    kw["max_iters"] = max(int(kw.get("max_iters", 4_000)) * 4, 8_000)
    ref_cfg = ExecConfig(backend=exec_cfg.backend, engine=exec_cfg.engine,
                         solver_kw=kw)
    problem = spec.make_problem(inst)
    res = pop_mod.solve_full_ex(problem, exec_cfg=ref_cfg)
    alloc = res.alloc
    if spec.round is not None:
        alloc = spec.round(inst, res.alloc)
    return spec.quality_of(spec.metrics_of(inst, problem, alloc))


def run(fast: bool = False, rounds: int = None, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    rounds = rounds or (4 if fast else 8)

    t0 = time.perf_counter()
    profile = build_profile(domains=("gavel", "traffic"), fast=True,
                            seed=seed, measure_launch=False,
                            measure_backends=False)
    profile.digest = profile_digest(profile)   # seal (save_profile's job
    profile_s = time.perf_counter() - t0       # when the artifact is written)
    emit("tuning_profile_build", profile_s * 1e6,
         f"domains={len(profile.domains)}")

    tuned_svc = PopService(profile=profile)
    static_svc = PopService()
    out = {"profile_build_s": round(profile_s, 2), "slo": SLO.max_quality_loss,
           "rounds": rounds, "domains": {}}

    for domain, inst, drift, exec_cfg in _scenarios(fast, rng):
        spec = registry_mod.get(domain)
        arms = {}
        for label, svc, solve, slo in (
                ("static", static_svc, SolveConfig(), None),
                ("tuned", tuned_svc, None, SLO)):
            if slo is None:
                sess = svc.session(f"{domain}-static", inst, domain=domain,
                                   solve=solve, exec=exec_cfg)
            else:
                sess = svc.session(f"{domain}-tuned", inst, domain=domain,
                                   exec=exec_cfg, slo=slo)
            sess.step(inst)               # warmup (cold solve + compiles)
            cur = inst
            t1 = time.perf_counter()
            stepped = []
            for _ in range(rounds):
                cur = drift(cur)
                stepped.append((cur, sess.step(cur)))
            wall = time.perf_counter() - t1
            # realized quality vs the per-round capped full solve
            rels = []
            for step_inst, alloc in stepped:
                q = spec.quality_of(alloc.metrics)
                q_ref = _ref_quality(spec, step_inst, exec_cfg)
                if q is not None and q_ref:
                    rels.append(q / q_ref)
            arms[label] = {
                "steps_per_sec": round(rounds / wall, 3),
                "k": int(stepped[-1][1].k),
                "rel_quality_mean": round(float(np.mean(rels)), 4),
                "meets_slo": bool(np.mean(rels) >= 1.0 - SLO.max_quality_loss),
            }
        speedup = arms["tuned"]["steps_per_sec"] / \
            max(arms["static"]["steps_per_sec"], 1e-9)
        emit(f"tuning_{domain}",
             1e6 / max(arms["tuned"]["steps_per_sec"], 1e-9),
             f"tuned_k={arms['tuned']['k']};static_k={arms['static']['k']};"
             f"speedup={speedup:.2f};"
             f"tuned_rel_q={arms['tuned']['rel_quality_mean']:.3f};"
             f"meets_slo={arms['tuned']['meets_slo']}")
        out["domains"][domain] = {**arms, "tuned_vs_static_speedup":
                                  round(speedup, 3)}

    out["tuned_service_stats"] = {
        k: v for k, v in tuned_svc.stats().items()
        if k in ("slo_violations", "retunes", "steps", "plan_hit_rate")}
    save_json("tuning", out)
    return out


if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args()
    print(run(fast=args.fast, rounds=args.rounds))
