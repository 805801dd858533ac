"""Paper Fig. 5: E-Store query load balancing MILP.

Relax-and-round full problem vs POP-k (server-group split) vs E-Store
greedy: shard movement + runtime + balance feasibility.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.problems.load_balancing import (LoadBalanceProblem, estore_greedy,
                                           make_shard_workload)
from .common import Timer, emit, save_json, use_compile_cache

SOLVER_KW = dict(max_iters=12_000, tol_primal=1e-4, tol_gap=1e-4)


def run(n_shards: int = 1024, n_servers: int = 64, ks=(2, 4, 8, 16),
        seed: int = 0) -> dict:
    wl = make_shard_workload(n_shards, n_servers, seed=seed)
    prob = LoadBalanceProblem(wl)
    rows = []

    full = prob.solve_full(solver_kw=SOLVER_KW)
    rows.append(dict(method="full", k=1, solve_s=full.solve_time_s,
                     movement=full.movement, max_load_dev=full.max_load_dev,
                     feasible=full.feasible))
    emit("load_balance_full", full.solve_time_s * 1e6,
         f"movement={full.movement:.1f};dev={full.max_load_dev:.3f};"
         f"feasible={full.feasible}")

    for k in ks:
        r = prob.pop_solve(k, seed=seed, solver_kw=SOLVER_KW)
        speedup = full.solve_time_s / r.solve_time_s
        rows.append(dict(method=f"pop{k}", k=k, solve_s=r.solve_time_s,
                         movement=r.movement, max_load_dev=r.max_load_dev,
                         feasible=r.feasible, speedup=speedup))
        emit(f"load_balance_pop{k}", r.solve_time_s * 1e6,
             f"speedup={speedup:.1f}x;movement={r.movement:.1f};"
             f"rel_movement={r.movement/max(full.movement,1e-9):.3f};"
             f"feasible={r.feasible}")

    with Timer() as t:
        g = estore_greedy(wl)
    ev = prob.evaluate(g)
    rows.append(dict(method="greedy", k=0, solve_s=t.seconds,
                     movement=ev["movement"],
                     max_load_dev=ev["max_load_dev"],
                     feasible=ev["load_feasible"] and ev["mem_feasible"]))
    emit("load_balance_greedy", t.seconds * 1e6,
         f"movement={ev['movement']:.1f};dev={ev['max_load_dev']:.3f};"
         f"feasible={ev['load_feasible'] and ev['mem_feasible']}")

    out = {"n_shards": n_shards, "n_servers": n_servers, "rows": rows}
    save_json("load_balancing", out)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-shards", type=int, default=1024)
    ap.add_argument("--n-servers", type=int, default=64)
    a = ap.parse_args()
    run(n_shards=a.n_shards, n_servers=a.n_servers)


if __name__ == "__main__":
    use_compile_cache()
    main()
