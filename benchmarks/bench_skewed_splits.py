"""Paper Fig. 6: skewed vs self-similar sub-problems (traffic engineering).

Skewed = all commodities sharing a source node land in the same
sub-problem; self-similar = random.  The paper shows the skewed split
loses substantial flow; replication (§4.3) is additionally evaluated on a
hot-entity variant.
"""

from __future__ import annotations

import numpy as np

from repro.core import (ExecConfig, SolveConfig, pop, skewed_partition,
                        similarity_report)
from repro.problems.traffic_engineering import cspf_heuristic
from .bench_traffic_engineering import build, SOLVER_KW
from .common import emit, save_json, use_compile_cache


def run(n_demands: int = 10_000, ks=(4, 16), seed: int = 0) -> dict:
    prob = build(n_demands=n_demands, seed=seed)
    rows = []
    fr = pop.solve_full_ex(prob, exec_cfg=ExecConfig(solver_kw=SOLVER_KW))
    full, t_full = fr.alloc, fr.solve_time_s
    opt = prob.evaluate(full)["total_flow"]

    for k in ks:
        r_rand = pop.solve_instance(
            prob, SolveConfig(k=k, strategy="random", seed=seed),
            ExecConfig(solver_kw=SOLVER_KW))
        f_rand = prob.evaluate(r_rand.alloc)["total_flow"]
        idx = skewed_partition(prob.source_groups(), k)
        r_skew = pop.solve_instance(
            prob, SolveConfig(k=k), ExecConfig(solver_kw=SOLVER_KW),
            partition_idx=idx)
        f_skew = prob.evaluate(r_skew.alloc)["total_flow"]
        sim_r = r_rand.similarity["max_mean_dist"]
        sim_s = r_skew.similarity["max_mean_dist"]
        rows.append(dict(k=k, flow_random=f_rand, flow_skewed=f_skew,
                         rel_random=f_rand / opt, rel_skewed=f_skew / opt,
                         sim_random=sim_r, sim_skewed=sim_s))
        emit(f"skew_split_k{k}", r_skew.solve_time_s * 1e6,
             f"rel_flow_random={f_rand/opt:.4f};rel_flow_skewed={f_skew/opt:.4f};"
             f"simdist_random={sim_r:.3f};simdist_skewed={sim_s:.3f}")

    out = {"opt_flow": opt, "rows": rows}
    save_json("skewed_splits", out)
    return out


def main():
    run()


if __name__ == "__main__":
    use_compile_cache()
    main()
