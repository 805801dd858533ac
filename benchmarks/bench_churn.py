"""Churn-aware warm starts: warm-vs-cold iterations under entity churn.

PR-2's warm start required the instance SHAPE to be stable; the PopPlan
layer (``core/plan.py``) remaps the previous iterates across entity
arrivals/departures instead.  This benchmark measures what that buys: for
each paper domain, a base instance is solved cold, then re-solved at
5/20/50% entity churn (that fraction of entities replaced by fresh ones,
survivors' data jittered a few percent) both COLD and WARM via
``pop_solve(warm=prev, entity_ids=...)``.

The cold control shares the warm solve's plan/grouping (the same control
``bench_online_resolve`` uses), so the measured delta is the warm start
itself, not partition luck.  Expectation: warm well under cold at <=20%
churn on all three domains, degrading gracefully toward (and possibly
past) 1.0x at 50%.

    PYTHONPATH=src python -m benchmarks.bench_churn [--fast]
"""

from __future__ import annotations

import argparse
import dataclasses

import numpy as np

from repro.core import ExecConfig, SolveConfig, pop
from repro.problems.cluster_scheduling import GavelProblem, make_cluster_workload
from repro.problems.load_balancing import (LoadBalanceProblem, ShardWorkload,
                                           make_shard_workload)
from repro.problems.traffic_engineering import (TrafficProblem,
                                                k_shortest_paths,
                                                make_demands, make_topology)
from .common import emit, save_json, use_compile_cache

CHURN_LEVELS = (0.05, 0.2, 0.5)


def _row(domain, level, cold_iters, warm_iters, warm_fraction, converged):
    ratio = warm_iters / max(cold_iters, 1)
    emit(f"churn_{domain}_{int(level * 100)}pct", ratio * 1e6,
         f"cold={cold_iters};warm={warm_iters};wf={warm_fraction:.2f}")
    return dict(churn=level, cold_iters=int(cold_iters),
                warm_iters=int(warm_iters), iter_ratio=float(ratio),
                warm_fraction=float(warm_fraction),
                converged=bool(converged))


def run_cluster(n_jobs: int = 192, k: int = 8, n_seeds: int = 3,
                num_workers: tuple = (64, 64, 64),
                solver_kw: dict | None = None) -> dict:
    # keep the fleet CONTENDED (~1 worker per job per type): with abundant
    # workers the LP is slack, both solves finish in a few restarts, and
    # the warm-vs-cold signal washes out
    kw = dict(solver_kw or dict(max_iters=20_000, tol_primal=1e-4,
                                tol_gap=1e-4))
    wl = make_cluster_workload(n_jobs, num_workers=num_workers, seed=0)
    prob = GavelProblem(wl)
    ids = np.arange(n_jobs)
    prev = pop.solve_instance(prob, SolveConfig(k=k, strategy="stratified"),
                              ExecConfig(solver_kw=kw), entity_ids=ids)
    rows = []
    for level in CHURN_LEVELS:
        cold_t = warm_t = 0
        wf = 0.0
        conv = True
        for seed in range(n_seeds):
            rng = np.random.default_rng(1_000 * seed + int(level * 100))
            n_out = int(level * n_jobs)
            keep = np.arange(n_jobs)[n_out:]
            fresh = make_cluster_workload(n_out, num_workers=num_workers,
                                          seed=seed + 77)
            cat = lambda a, b: np.concatenate([a[keep], b])
            wl2 = dataclasses.replace(
                wl, T=cat(wl.T, fresh.T) * rng.uniform(0.98, 1.02, (n_jobs, 3)),
                w=cat(wl.w, fresh.w), z=cat(wl.z, fresh.z),
                interference=cat(wl.interference, fresh.interference),
                job_type=cat(wl.job_type, fresh.job_type))
            ids2 = np.concatenate([keep, 10_000 * (seed + 1) + np.arange(n_out)])
            prob2 = GavelProblem(wl2)
            warm = pop.solve_instance(prob2, SolveConfig(k=k, strategy="random"),
                                      ExecConfig(solver_kw=kw),
                                      warm=prev, entity_ids=ids2)
            cold = pop.solve_instance(prob2, SolveConfig(k=k),
                                      ExecConfig(solver_kw=kw), plan=warm.plan)
            cold_t += int(cold.iterations.sum())
            warm_t += int(warm.iterations.sum())
            wf += warm.warm_stats["warm_fraction"] / n_seeds
            conv &= bool(warm.converged.all())
        rows.append(_row("cluster", level, cold_t, warm_t, wf, conv))
    return dict(scenario="cluster_scheduling", n_jobs=n_jobs, k=k, rows=rows)


def run_traffic(n_demands: int = 512, k: int = 8, n_seeds: int = 3,
                solver_kw: dict | None = None) -> dict:
    kw = dict(solver_kw or dict(max_iters=20_000, tol_primal=1e-4,
                                tol_gap=1e-4))
    topo = make_topology(n_nodes=80, target_edges=190, seed=0)
    pool_n = 2 * n_demands
    pairs, size = make_demands(topo, pool_n, seed=0)
    paths = k_shortest_paths(topo, pairs, n_paths=3, max_len=24, seed=0)
    sel = np.arange(n_demands)
    prob = TrafficProblem(topo, pairs[sel], size[sel], paths[sel])
    prev = pop.solve_instance(prob, SolveConfig(k=k, strategy="random"),
                              ExecConfig(solver_kw=kw), entity_ids=sel)
    rows = []
    for level in CHURN_LEVELS:
        cold_t = warm_t = 0
        wf = 0.0
        conv = True
        for seed in range(n_seeds):
            rng = np.random.default_rng(2_000 * seed + int(level * 100))
            n_out = int(level * n_demands)
            keep = sel[n_out:]
            newcomers = rng.choice(np.arange(n_demands, pool_n), n_out,
                                   replace=False)
            sel2 = np.concatenate([keep, newcomers])
            prob2 = TrafficProblem(
                topo, pairs[sel2],
                size[sel2] * rng.uniform(0.97, 1.03, n_demands), paths[sel2])
            warm = pop.solve_instance(prob2, SolveConfig(k=k, strategy="random"),
                                      ExecConfig(solver_kw=kw),
                                      warm=prev, entity_ids=sel2)
            cold = pop.solve_instance(prob2, SolveConfig(k=k),
                                      ExecConfig(solver_kw=kw), plan=warm.plan)
            cold_t += int(cold.iterations.sum())
            warm_t += int(warm.iterations.sum())
            wf += warm.warm_stats["warm_fraction"] / n_seeds
            conv &= bool(warm.converged.all())
        rows.append(_row("traffic", level, cold_t, warm_t, wf, conv))
    return dict(scenario="traffic_engineering", n_demands=n_demands, k=k,
                rows=rows)


def run_load_balancing(n_shards: int = 512, n_servers: int = 16, k: int = 4,
                       n_seeds: int = 3,
                       solver_kw: dict | None = None) -> dict:
    kw = dict(solver_kw or dict(max_iters=12_000, tol_primal=1e-4,
                                tol_gap=1e-4))
    # eps_frac 0.15 and >=32 shards per server: keeps the zipf tails
    # FEASIBLE at every churn level — near-infeasible instances (a single
    # capped-zipf shard above the load window) grind both solves to the
    # iteration cap and drown the warm-start signal in noise
    wl = make_shard_workload(n_shards, n_servers, eps_frac=0.15, seed=0)
    wl = dataclasses.replace(wl, ids=np.arange(n_shards))
    prev = LoadBalanceProblem(wl).pop_solve(k, solver_kw=kw)
    pool = make_shard_workload(2 * n_shards, n_servers, eps_frac=0.15, seed=9)
    rows = []
    for level in CHURN_LEVELS:
        cold_t = warm_t = 0
        wf = 0.0
        for seed in range(n_seeds):
            rng = np.random.default_rng(3_000 * seed + int(level * 100))
            n_out = int(level * n_shards)
            keep = np.sort(rng.choice(n_shards, n_shards - n_out,
                                      replace=False))
            new = rng.choice(2 * n_shards, n_out, replace=False)
            wl2 = ShardWorkload(
                load=np.concatenate([wl.load[keep], pool.load[new]])
                     * rng.uniform(0.97, 1.03, n_shards),
                mem=np.concatenate([wl.mem[keep], pool.mem[new]]),
                placement=np.concatenate([prev.placement[keep],
                                          rng.integers(0, n_servers, n_out)]),
                cap=wl.cap, eps_frac=wl.eps_frac,
                ids=np.concatenate([keep, 10_000 * (seed + 1)
                                    + np.arange(n_out)]))
            prob2 = LoadBalanceProblem(wl2)
            # cold control shares the grouping (warm minus the warm start)
            cold = prob2.pop_solve(k, solver_kw=kw, warm=prev,
                                   warm_start=False)
            warm = prob2.pop_solve(k, solver_kw=kw, warm=prev)
            cold_t += cold.extra["iterations"]
            warm_t += warm.extra["iterations"]
            wf += warm.extra["warm_fraction"] / n_seeds
        rows.append(_row("lb", level, cold_t, warm_t, wf, True))
    return dict(scenario="load_balancing", n_shards=n_shards,
                n_servers=n_servers, k=k, rows=rows)


def run(fast: bool = False) -> dict:
    if fast:
        cluster = run_cluster(n_jobs=96, k=4, n_seeds=2,
                              num_workers=(32, 32, 32))
        traffic = run_traffic(n_demands=256, k=4, n_seeds=2)
        lb = run_load_balancing(n_shards=128, n_servers=16, k=4, n_seeds=2)
    else:
        cluster = run_cluster()
        traffic = run_traffic()
        lb = run_load_balancing()
    out = {"cluster": cluster, "traffic": traffic, "load_balancing": lb}
    save_json("churn", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true", help="CI smoke sizes")
    args = ap.parse_args(argv)
    res = run(fast=args.fast)
    for dom, payload in res.items():
        for row in payload["rows"]:
            ok = "OK " if (row["iter_ratio"] <= 1.0 or row["churn"] > 0.2) \
                else "REGR"
            print(f"# {ok} {dom:>14s} churn={row['churn']:.2f} "
                  f"ratio={row['iter_ratio']:.2f} "
                  f"(cold={row['cold_iters']} warm={row['warm_iters']})")


if __name__ == "__main__":
    use_compile_cache()
    main()
