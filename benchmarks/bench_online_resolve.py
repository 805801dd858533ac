"""Online re-solves: cold vs warm-started POP on perturbed instances.

The paper's motivating setting is ONLINE: schedulers re-allocate every few
minutes as measured throughputs drift, balancers re-place shards as loads
shift.  Consecutive instances are tiny perturbations of each other, so the
previous solution is an excellent starting iterate — PDHG warm-starting
(``pop_solve(..., warm=prev)`` / ``LoadBalanceProblem.pop_solve(...,
warm=prev)``) should cut iteration counts by well over half at equal
solution quality.

Two scenarios, both measured as (cold re-solve, warm re-solve) on the SAME
perturbed instance with the SAME partition:

* cluster scheduling — Gavel LP, throughputs perturbed ±``perturb``
* load balancing     — §3.3 MILP relaxation, shard loads perturbed and the
  placement advanced to the previous solve's output (a real tick)

Timings use the jit-cached map solver (``backends.make_map_solver``), so
the cold/warm wall-clock delta is solver work, not retracing.

    PYTHONPATH=src python -m benchmarks.bench_online_resolve [--fast]
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from repro.core import ExecConfig, SolveConfig, pop
from repro.problems.cluster_scheduling import GavelProblem, make_cluster_workload
from repro.problems.load_balancing import LoadBalanceProblem, make_shard_workload
from .common import emit, save_json, use_compile_cache


def run_cluster(n_jobs: int = 256, k: int = 8, perturb: float = 0.03,
                n_rounds: int = 3, seed: int = 0,
                solver_kw: dict | None = None) -> dict:
    """Gavel scheduling rounds: round 0 cold, then ``n_rounds`` perturbed
    re-solves, each done both cold and warm on the identical instance."""
    kw = dict(solver_kw or dict(max_iters=20_000, tol_primal=1e-4,
                                tol_gap=1e-4))
    rng = np.random.default_rng(seed + 1000)
    wl = make_cluster_workload(n_jobs, num_workers=(64, 64, 64), seed=seed)
    prob = GavelProblem(wl, space_sharing=False)
    prev = pop.solve_instance(prob, SolveConfig(k=k, strategy="stratified"),
                              ExecConfig(solver_kw=kw))
    rows = [dict(round=0, mode="cold", solve_s=prev.solve_time_s,
                 iters=int(prev.iterations.sum()),
                 converged=bool(prev.converged.all()))]
    for rnd in range(1, n_rounds + 1):
        wl = dataclasses.replace(
            wl, T=wl.T * rng.uniform(1 - perturb, 1 + perturb, wl.T.shape))
        prob = GavelProblem(wl, space_sharing=False)
        cold = pop.solve_instance(prob, SolveConfig(k=k),
                                  ExecConfig(solver_kw=kw),
                                  partition_idx=prev.idx)
        warm = pop.solve_instance(prob, SolveConfig(k=k, strategy="random"),
                                  ExecConfig(solver_kw=kw), warm=prev)
        for mode, r in (("cold", cold), ("warm", warm)):
            rows.append(dict(round=rnd, mode=mode, solve_s=r.solve_time_s,
                             iters=int(r.iterations.sum()),
                             converged=bool(r.converged.all())))
        emit(f"online_cluster_round{rnd}_cold", cold.solve_time_s * 1e6,
             f"iters={int(cold.iterations.sum())}")
        emit(f"online_cluster_round{rnd}_warm", warm.solve_time_s * 1e6,
             f"iters={int(warm.iterations.sum())};"
             f"iter_ratio={warm.iterations.sum()/max(cold.iterations.sum(),1):.2f}")
        prev = warm
    return dict(scenario="cluster_scheduling", n_jobs=n_jobs, k=k,
                perturb=perturb, rows=rows)


def run_load_balancing(n_shards: int = 512, n_servers: int = 32, k: int = 4,
                       perturb: float = 0.05, n_rounds: int = 3,
                       seed: int = 0, solver_kw: dict | None = None) -> dict:
    """Balancer ticks: loads drift, the placement advances to the previous
    output, and each tick is re-solved cold and warm."""
    kw = dict(solver_kw or dict(max_iters=12_000, tol_primal=1e-4,
                                tol_gap=1e-4))
    rng = np.random.default_rng(seed + 2000)
    wl = make_shard_workload(n_shards, n_servers, seed=seed)
    prev = LoadBalanceProblem(wl).pop_solve(k, solver_kw=kw)
    rows = [dict(round=0, mode="cold", solve_s=prev.solve_time_s,
                 iters=prev.extra["iterations"],
                 movement=prev.movement, feasible=prev.feasible)]
    for rnd in range(1, n_rounds + 1):
        wl = dataclasses.replace(
            wl,
            load=wl.load * rng.uniform(1 - perturb, 1 + perturb, wl.load.shape),
            placement=prev.placement)
        prob = LoadBalanceProblem(wl)
        # cold control reuses the previous grouping (warm minus the warm
        # start) so both solves factor the instance identically
        cold = prob.pop_solve(k, solver_kw=kw, warm=prev, warm_start=False)
        warm = prob.pop_solve(k, solver_kw=kw, warm=prev)
        for mode, r in (("cold", cold), ("warm", warm)):
            rows.append(dict(round=rnd, mode=mode, solve_s=r.solve_time_s,
                             iters=r.extra["iterations"],
                             movement=r.movement, feasible=r.feasible))
        emit(f"online_lb_round{rnd}_cold", cold.solve_time_s * 1e6,
             f"iters={cold.extra['iterations']}")
        emit(f"online_lb_round{rnd}_warm", warm.solve_time_s * 1e6,
             f"iters={warm.extra['iterations']};"
             f"iter_ratio={warm.extra['iterations']/max(cold.extra['iterations'],1):.2f}")
        prev = warm
    return dict(scenario="load_balancing", n_shards=n_shards,
                n_servers=n_servers, k=k, perturb=perturb, rows=rows)


def run(fast: bool = False) -> dict:
    if fast:
        cluster = run_cluster(n_jobs=96, k=4, n_rounds=2)
        lb = run_load_balancing(n_shards=128, n_servers=16, k=4, n_rounds=2)
    else:
        cluster = run_cluster()
        lb = run_load_balancing()
    out = {"cluster": cluster, "load_balancing": lb}
    save_json("online_resolve", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true", help="CI smoke sizes")
    args = ap.parse_args(argv)
    run(fast=args.fast)


if __name__ == "__main__":
    use_compile_cache()
    main()
