"""Paper §4.3: hot-entity replication.

Traffic engineering with a heavy-tailed demand distribution (a few
'Taylor Swift' commodities holding a large share of total demand): without
replication, the sub-problem holding a hot commodity can only allocate it
1/k of each link; with replication the hot commodity spans several
sub-problems and its sub-allocations are summed.
"""

from __future__ import annotations

import numpy as np

from repro.core import ExecConfig, SolveConfig, pop
from repro.problems.traffic_engineering import (TrafficProblem,
                                                k_shortest_paths,
                                                make_demands, make_topology)
from .bench_traffic_engineering import SOLVER_KW
from .common import emit, save_json, use_compile_cache


def build_hot(n_demands=5_000, hot_frac=0.002, hot_boost=200.0, seed=0):
    topo = make_topology(n_nodes=200, target_edges=480, seed=seed)
    pairs, dem = make_demands(topo, n_demands, seed=seed + 1)
    rng = np.random.default_rng(seed + 2)
    n_hot = max(1, int(hot_frac * n_demands))
    hot = rng.choice(n_demands, n_hot, replace=False)
    dem[hot] *= hot_boost
    pe = k_shortest_paths(topo, pairs, n_paths=4, max_len=48, seed=seed + 3)
    return TrafficProblem(topo, pairs, dem, pe)


def run(k: int = 16, seed: int = 0) -> dict:
    prob = build_hot(seed=seed)
    fr = pop.solve_full_ex(prob, exec_cfg=ExecConfig(solver_kw=SOLVER_KW))
    full, t_full = fr.alloc, fr.solve_time_s
    opt = prob.evaluate(full)["total_flow"]

    r_plain = pop.solve_instance(
        prob, SolveConfig(k=k, strategy="random", seed=seed),
        ExecConfig(solver_kw=SOLVER_KW))
    f_plain = prob.evaluate(r_plain.alloc)["total_flow"]

    r_rep = pop.solve_instance(
        prob, SolveConfig(k=k, strategy="random", seed=seed,
                          replicate_threshold=0.5),
        ExecConfig(solver_kw=SOLVER_KW))
    f_rep = prob.evaluate(r_rep.alloc)["total_flow"]

    emit(f"replication_off_k{k}", r_plain.solve_time_s * 1e6,
         f"rel_flow={f_plain/opt:.4f}")
    emit(f"replication_on_k{k}", r_rep.solve_time_s * 1e6,
         f"rel_flow={f_rep/opt:.4f};replicas={r_rep.replication.n_expanded}")

    out = {"opt_flow": opt, "k": k, "flow_plain": f_plain, "flow_rep": f_rep,
           "n_expanded": int(r_rep.replication.n_expanded)}
    save_json("replication", out)
    return out


def main():
    run()


if __name__ == "__main__":
    use_compile_cache()
    main()
