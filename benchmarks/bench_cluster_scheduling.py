"""Paper Fig. 3: Gavel max-min fairness with space sharing.

Full LP vs POP-k vs Gandiva-like heuristic: runtime + mean/min normalised
throughput.  Paper claims: 0.3% mean-quality loss at 405x runtime
improvement; heuristic quality far worse (on the fairness metric).

Default scale is CPU-budgeted (single-core container); ``--paper-scale``
runs the full 10^6-job-combination configuration.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.core import ExecConfig, SolveConfig, pop
from repro.problems.cluster_scheduling import (GavelProblem,
                                               gandiva_heuristic,
                                               make_cluster_workload)
from .common import Timer, emit, save_json, use_compile_cache

SOLVER_KW = dict(max_iters=12_000, tol_primal=1e-4, tol_gap=1e-4)


def run(n_jobs: int = 448, workers=(256, 256, 256), ks=(4, 8, 16, 32),
        space_sharing: bool = True, seed: int = 0) -> dict:
    wl = make_cluster_workload(n_jobs, num_workers=workers, seed=seed)
    prob = GavelProblem(wl, space_sharing=space_sharing)
    n_combos = n_jobs + n_jobs * (n_jobs - 1) // 2 if space_sharing else n_jobs

    rows = []
    with Timer() as t:
        fr = pop.solve_full_ex(prob, exec_cfg=ExecConfig(solver_kw=SOLVER_KW))
        full, t_solve = fr.alloc, fr.solve_time_s
    ev = prob.evaluate(full)
    full_mean = ev["mean_norm_throughput"]
    rows.append(dict(method="full", k=1, solve_s=t_solve, **ev))
    emit("cluster_sched_full", t_solve * 1e6,
         f"mean={ev['mean_norm_throughput']:.4f};min={ev['min_norm_throughput']:.4f}")

    for k in ks:
        r = pop.solve_instance(prob, SolveConfig(k=k, strategy="stratified"),
                               ExecConfig(solver_kw=SOLVER_KW))
        ev = prob.evaluate(r.alloc)
        speedup = t_solve / r.solve_time_s
        quality = ev["mean_norm_throughput"] / full_mean
        rows.append(dict(method=f"pop{k}", k=k, solve_s=r.solve_time_s,
                         speedup=speedup, rel_quality=quality, **ev))
        emit(f"cluster_sched_pop{k}", r.solve_time_s * 1e6,
             f"speedup={speedup:.1f}x;rel_mean_quality={quality:.4f};"
             f"min={ev['min_norm_throughput']:.4f}")

    with Timer() as t:
        rho_h = gandiva_heuristic(wl, space_sharing=space_sharing)
    ev = prob.evaluate(rho_h)
    rows.append(dict(method="gandiva", k=0, solve_s=t.seconds, **ev))
    emit("cluster_sched_gandiva", t.seconds * 1e6,
         f"mean={ev['mean_norm_throughput']:.4f};min={ev['min_norm_throughput']:.4f}")

    out = {"n_jobs": n_jobs, "n_combos": n_combos, "rows": rows}
    save_json("cluster_scheduling", out)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper-scale", action="store_true",
                    help="1414 jobs -> 10^6 combos (minutes-to-hours on CPU)")
    ap.add_argument("--n-jobs", type=int, default=None)
    a = ap.parse_args()
    n = a.n_jobs or (1414 if a.paper_scale else 448)
    run(n_jobs=n)


if __name__ == "__main__":
    use_compile_cache()
    main()
