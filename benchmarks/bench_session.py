"""Multi-tenant PopService session throughput.

One service, several tenants across all four registered domains, steps
interleaved (the serving pattern: every tenant's instance drifts each
round, one churns periodically).  Reports steps/sec after the warmup
round, p50/p99 step latency, the plan-cache hit rate, and the mean warm
fraction — the observability the session layer added, aggregated by the
service itself.

A fault-injection phase (``repro.analysis.faults``) then drives one
tenant through the degradation ladder — poisoned warm iterates, a dropped
plan, a deadline under inflated solve rates — and reports the
degraded/recovered/fallback counters plus fault-step latency, so the
robustness layer's overhead and behavior are tracked PR-over-PR alongside
the happy path.

    PYTHONPATH=src python -m benchmarks.bench_session [--fast]
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np

from repro.core import ExecConfig, SolveConfig
from repro.domains import (BalanceInstance, GavelInstance,
                           make_placement_instance)
from repro.problems.cluster_scheduling import make_cluster_workload
from repro.problems.traffic_engineering import (TrafficProblem,
                                                k_shortest_paths,
                                                make_demands, make_topology)
from repro.service import PopService
from .common import emit, save_json, use_compile_cache


def _tenants(fast: bool, rng):
    """(name, first instance, drift fn, SolveConfig, ExecConfig) per
    tenant — two traffic nets, a scheduler fleet, a balancer, an MoE
    fleet: the interleaved-tenant mix a serving-side PopService sees."""
    kw = dict(max_iters=1_500 if fast else 4_000, tol_primal=1e-4,
              tol_gap=1e-4)
    n_dem = 200 if fast else 1_000
    n_jobs = 48 if fast else 128
    n_groups = 40 if fast else 96
    out = []

    for t in range(2):
        topo = make_topology(16, 36, seed=t)
        pairs, dem = make_demands(topo, n_dem, seed=t)
        pe = k_shortest_paths(topo, pairs, n_paths=2, max_len=10, seed=t)
        prob = TrafficProblem(topo, pairs, dem, pe)

        def drift_traffic(inst, rng=rng):
            return TrafficProblem(
                inst.topo, inst.pairs,
                inst.demand * rng.uniform(0.97, 1.03, inst.demand.shape[0]),
                inst.path_edges)
        out.append((f"net-{t}", prob, drift_traffic,
                    SolveConfig(k=4, strategy="stratified"),
                    ExecConfig(solver_kw=kw)))

    wl = make_cluster_workload(n_jobs, seed=7)
    ginst = GavelInstance(wl, job_ids=np.arange(n_jobs))

    def drift_gavel(inst, rng=rng):
        wl2 = dataclasses.replace(
            inst.wl, T=inst.wl.T * rng.uniform(0.95, 1.05, inst.wl.T.shape))
        return GavelInstance(wl2, job_ids=inst.job_ids)
    out.append(("fleet", ginst, drift_gavel,
                SolveConfig(k=4, strategy="stratified", min_per_sub=8),
                ExecConfig(solver_kw=kw)))

    binst = BalanceInstance(load=rng.uniform(1, 8, n_groups), n_targets=8,
                            ids=np.arange(n_groups), eps_frac=0.25)

    def drift_balance(inst, rng=rng):
        # periodic churn: 10% of groups finish, fresh ones arrive
        n = inst.load.shape[0]
        n_churn = n // 10
        keep = np.arange(n_churn, n)
        return BalanceInstance(
            load=np.concatenate([inst.load[keep] * rng.uniform(0.97, 1.03,
                                                               keep.size),
                                 rng.uniform(1, 8, n_churn)]),
            n_targets=inst.n_targets, eps_frac=inst.eps_frac,
            ids=np.concatenate([inst.ids[keep],
                                inst.ids.max() + 1 + np.arange(n_churn)]))
    out.append(("balancer", binst, drift_balance, SolveConfig(k=2),
                ExecConfig(solver_kw=dict(max_iters=1_500 if fast
                                          else 6_000))))

    minst = make_placement_instance(64 if fast else 128, 8, seed=9)
    minst.ids = np.arange(minst.n_experts)

    def drift_moe(inst, rng=rng):
        return dataclasses.replace(
            inst, load=inst.load * rng.uniform(0.95, 1.05,
                                               inst.load.shape[0]))
    out.append(("moe-fleet", minst, drift_moe,
                SolveConfig(k=4, strategy="stratified", min_per_sub=8),
                ExecConfig(solver_kw=kw)))
    return out


def run(fast: bool = False, rounds: int = None, seed: int = 0) -> dict:
    rng = np.random.default_rng(seed)
    rounds = rounds or (3 if fast else 6)
    service = PopService()
    tenants = _tenants(fast, rng)
    insts = {}
    for name, inst, _, solve_cfg, exec_cfg in tenants:
        service.session(name, inst, solve=solve_cfg, exec=exec_cfg)
        insts[name] = inst

    # warmup round: cold solves + jit compilation (excluded from rate)
    t0 = time.perf_counter()
    for name, inst, _, _, _ in tenants:
        service.session(name).step(inst)
    warmup_s = time.perf_counter() - t0

    # interleaved steady-state rounds: all tenants drift every round
    t1 = time.perf_counter()
    n_steps = 0
    per_tenant = {name: [] for name, *_ in tenants}
    step_walls = []
    for _ in range(rounds):
        for name, _, drift, _, _ in tenants:
            insts[name] = drift(insts[name])
            ts = time.perf_counter()
            a = service.session(name).step(insts[name])
            step_walls.append(time.perf_counter() - ts)
            per_tenant[name].append(a.solve_time_s)
            n_steps += 1
    steady_s = time.perf_counter() - t1
    p50 = float(np.percentile(step_walls, 50))
    p99 = float(np.percentile(step_walls, 99))

    stats = service.stats()
    steps_per_sec = n_steps / steady_s
    emit("session_steady_steps", steady_s / n_steps * 1e6,
         f"steps_per_sec={steps_per_sec:.2f};"
         f"plan_hit_rate={stats['plan_hit_rate']:.2f};"
         f"warm_fraction={stats['warm_fraction_mean']:.3f}")
    emit("session_step_latency_p50", p50 * 1e6, f"p99_us={p99 * 1e6:.0f}")
    emit("session_warmup_round", warmup_s / len(tenants) * 1e6,
         f"tenants={len(tenants)}")
    for name in per_tenant:
        emit(f"session_tenant_{name}",
             float(np.mean(per_tenant[name])) * 1e6,
             f"steps={len(per_tenant[name])}")

    fault = _fault_phase(service, insts, tenants)

    out = {
        "tenants": len(tenants), "rounds": rounds,
        "warmup_s": round(warmup_s, 3), "steady_s": round(steady_s, 3),
        "steps_per_sec": round(steps_per_sec, 3),
        "step_latency_p50_s": round(p50, 4),
        "step_latency_p99_s": round(p99, 4),
        "faults": fault,
        "service_stats": {k: (round(v, 4) if isinstance(v, float) else v)
                          for k, v in service.stats().items()},
        "per_tenant_mean_s": {k: round(float(np.mean(v)), 4)
                              for k, v in per_tenant.items()},
    }
    save_json("session", out)
    return out


def _fault_phase(service, insts, tenants) -> dict:
    """Push one traffic tenant down the degradation ladder and time every
    rung (docs/ROBUSTNESS.md): lane quarantine, warm-state mismatch, and a
    deadline fallback under inflated solve rates."""
    from repro.analysis import faults as fj

    name, _, drift = next((n, i, d) for n, i, d, *_ in tenants
                          if n.startswith("net"))
    sess = service.session(name)
    statuses, walls = [], []

    def _step(deadline_s=None):
        insts[name] = drift(insts[name])
        ts = time.perf_counter()
        a = sess.step(insts[name], deadline_s=deadline_s)
        walls.append(time.perf_counter() - ts)
        statuses.append(a.status)
        return a

    fj.poison_warm(sess, lanes=[1])
    _step()                                   # -> recovered (quarantine)
    fj.drop_warm_plan(sess)
    _step()                                   # -> recovered (mismatch)
    saved = dict(service._rates)
    fj.inflate_rates(service, factor=1e9)
    _step(deadline_s=0.25)                    # -> fallback (deadline)
    service._rates.clear()
    service._rates.update(saved)
    _step()                                   # -> ok (ladder exits clean)

    counts = {s: statuses.count(s)
              for s in ("ok", "degraded", "recovered", "fallback")}
    emit("session_fault_step", float(np.mean(walls)) * 1e6,
         f"recovered={counts['recovered']};fallback={counts['fallback']};"
         f"final={statuses[-1]}")
    return {"statuses": statuses, "counts": counts,
            "mean_fault_step_s": round(float(np.mean(walls)), 4)}


if __name__ == "__main__":
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--rounds", type=int, default=None)
    args = ap.parse_args()
    print(run(fast=args.fast, rounds=args.rounds))
