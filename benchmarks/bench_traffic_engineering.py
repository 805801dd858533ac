"""Paper Fig. 4: WAN traffic engineering on a KDL-like topology
(754 nodes / 1790 edges).  Full max-flow LP vs POP-k vs CSPF.

Paper claims: POP-64 within 1.5% of optimal flow, ~100x faster; beats CSPF.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core import ExecConfig, SolveConfig, pop
from repro.problems.traffic_engineering import (TrafficProblem,
                                                cspf_heuristic, k_shortest_paths,
                                                make_demands, make_topology)
from .common import Timer, emit, save_json, use_compile_cache

SOLVER_KW = dict(max_iters=10_000, tol_primal=1e-4, tol_gap=1e-4)


def build(n_nodes=754, n_edges=1790, n_demands=20_000, n_paths=4, seed=0):
    topo = make_topology(n_nodes=n_nodes, target_edges=n_edges, seed=seed)
    pairs, dem = make_demands(topo, n_demands, seed=seed + 1)
    pe = k_shortest_paths(topo, pairs, n_paths=n_paths, max_len=64,
                          seed=seed + 2)
    return TrafficProblem(topo, pairs, dem, pe)


def run(n_demands: int = 20_000, ks=(4, 16, 64), seed: int = 0) -> dict:
    prob = build(n_demands=n_demands, seed=seed)
    rows = []

    fr = pop.solve_full_ex(prob, exec_cfg=ExecConfig(solver_kw=SOLVER_KW))
    full, t_solve = fr.alloc, fr.solve_time_s
    ev = prob.evaluate(full)
    opt_flow = ev["total_flow"]
    rows.append(dict(method="full", k=1, solve_s=t_solve, **ev))
    emit("traffic_eng_full", t_solve * 1e6,
         f"flow={opt_flow:.1f};util={ev['max_edge_util']:.3f}")

    for k in ks:
        r = pop.solve_instance(
            prob, SolveConfig(k=k, strategy="random", seed=seed),
            ExecConfig(solver_kw=SOLVER_KW))
        ev = prob.evaluate(r.alloc)
        speedup = t_solve / r.solve_time_s
        rel = ev["total_flow"] / opt_flow
        rows.append(dict(method=f"pop{k}", k=k, solve_s=r.solve_time_s,
                         speedup=speedup, rel_flow=rel, **ev))
        emit(f"traffic_eng_pop{k}", r.solve_time_s * 1e6,
             f"speedup={speedup:.1f}x;rel_flow={rel:.4f};"
             f"util={ev['max_edge_util']:.3f}")

    with Timer() as t:
        f = cspf_heuristic(prob)
    ev = prob.evaluate(f)
    rows.append(dict(method="cspf", k=0, solve_s=t.seconds, **ev))
    emit("traffic_eng_cspf", t.seconds * 1e6,
         f"flow={ev['total_flow']:.1f};rel_flow={ev['total_flow']/opt_flow:.4f}")

    out = {"n_demands": n_demands, "rows": rows, "opt_flow": opt_flow}
    save_json("traffic_engineering", out)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-scale", action="store_true",
                    help="5x10^5 demands (paper scale; slow on one core)")
    ap.add_argument("--n-demands", type=int, default=None)
    a = ap.parse_args()
    n = a.n_demands or (500_000 if a.paper_scale else 20_000)
    run(n_demands=n)


if __name__ == "__main__":
    use_compile_cache()
    main()
