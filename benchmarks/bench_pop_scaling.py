"""Paper §2.4: runtime scaling in k, per map-step execution backend.

Measures POP map-step runtime vs k on a fixed cluster-scheduling instance
and fits the empirical exponent: the paper predicts superlinear speedup
(k^(2a-1) serial; sub-problems here solve as one vmap batch, so the
observed exponent blends the k^2 variable reduction with PDHG's
iteration-count advantage on smaller, better-conditioned problems).

``--backend`` sweeps execution backends from the ``core/backends.py``
registry (default: vmap, chunked_vmap, shard_map) so the scaling curve is
recorded per backend — the data that justifies ``backend="auto"``'s
selection thresholds on each platform.

``--engine-sweep`` (also part of the default run) A/Bs the PDHG *step
engines*: the generic operator-matvec engine vs the fused dense engine on
batched dense LPs, AND vs the ``fused_structured`` gather/segment-reduce
engine on real Gavel sub-problem stacks (singleton combos — the ISSUE
acceptance signal: structured-fused must never lose to matvec at k >= 2),
plus an in-loop-KKT vs standalone-KKT A/B (convergence checks from
carried half-step products cost zero extra operator passes).  Timings are
min-of-N after a compile warmup, so they measure the steady-state map
step — what an online solver with a jit-cached engine actually pays.

Also benchmarks the PDHG solver itself against scipy (HiGHS) on random
dense LPs — the solver-substrate sanity check.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from scipy.optimize import linprog

from repro.core import (ExecConfig, LinearProgram, SolveConfig,
                        backends as backends_mod, pdhg, pop)
from repro.problems.cluster_scheduling import GavelProblem, make_cluster_workload
from .common import Timer, emit, save_json, use_compile_cache

DEFAULT_BACKENDS = ("vmap", "chunked_vmap", "shard_map")
DEFAULT_KS = (1, 2, 4, 8, 16, 32)


def _random_dense_stack(k: int, n: int, mi: int, rng) -> pdhg.OperatorLP:
    """k random bounded-feasible dense LPs, stacked (the fused engine's
    home turf: dense data, block-padded by LinearProgram.build)."""
    lps = []
    for _ in range(k):
        c = rng.normal(size=n)
        G = rng.normal(size=(mi, n))
        h = G @ rng.uniform(0.2, 0.8, n) + rng.uniform(0.1, 1.0, mi)
        lps.append(LinearProgram.build(c=c, G=G, h=h,
                                       l=np.zeros(n), u=np.ones(n)))
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[pdhg.dense_ops(lp) for lp in lps])


def _ab_time(fns: dict, batch, repeats: int):
    """Interleaved min-of-N timing of competing jitted solvers on one
    batch: compile-warm every contender first, then interleave the timed
    rounds so machine-load drift hits all contenders equally, keeping the
    min per contender.  The ONE timing protocol for every A/B sweep in
    this file.  Returns (best_seconds, results) keyed like ``fns``."""
    results = {}
    for fn in fns.values():
        jax.block_until_ready(fn(batch).x)           # compile warmup
    best = {name: float("inf") for name in fns}
    for _ in range(repeats):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            res = fn(batch)
            jax.block_until_ready(res.x)
            best[name] = min(best[name], time.perf_counter() - t0)
            results[name] = res
    return best, results


def engine_sweep(ks=DEFAULT_KS, n: int = 150, mi: int = 90,
                 repeats: int = 9, max_iters: int = 2_000,
                 seed: int = 0) -> list:
    """Fused vs matvec engine on batched dense solves, per k.

    Both engines run the identical algorithm through ``solve_stacked`` via
    the jit-cached map solver, so the delta is pure step-execution cost.
    Returns rows [{engine, k, solve_s, iters}, ...]."""
    rng = np.random.default_rng(seed)
    kw = dict(max_iters=max_iters, tol_primal=1e-6, tol_gap=1e-6)
    rows = []
    for k in ks:
        ops = _random_dense_stack(k, n, mi, rng)
        batch = (ops, *backends_mod.cold_start(ops))
        fns = {
            name: backends_mod.make_map_solver(
                pdhg.dense_K_mv, pdhg.dense_KT_mv, kw,
                name if name == "matvec" else pdhg.fused_dense_engine())
            for name in ("matvec", "fused")
        }
        best, results = _ab_time(fns, batch, repeats)
        for name in fns:
            iters = int(np.asarray(results[name].iterations).sum())
            rows.append(dict(engine=name, k=k, solve_s=best[name],
                             iters=iters))
            emit(f"pop_engine_{name}_k{k}", best[name] * 1e6,
                 f"iters={iters}")
    return rows


def structured_engine_sweep(ks=(1, 2, 4, 8, 16), n_jobs: int = 256,
                            repeats: int = 7, max_iters: int = 2_000,
                            seed: int = 0) -> list:
    """fused_structured vs matvec on REAL Gavel sub-problem stacks
    (singleton combos — the per-job segment-sum operator), per k.

    ISSUE acceptance: fused_structured must beat matvec at every k >= 2
    (never slower) — its gather-ELL form has no scatters and one launch
    per half-step for the whole stack, where the matvec engine pays k
    vmapped ``segment_sum`` scatter-adds.  Interleaved min-of-N timing.
    Returns rows [{engine, k, solve_s, iters}, ...]."""
    wl = make_cluster_workload(n_jobs, num_workers=(64, 64, 64), seed=seed)
    prob = GavelProblem(wl, space_sharing=False)
    kw = dict(max_iters=max_iters, tol_primal=1e-6, tol_gap=1e-6)
    rows = []
    for k in ks:
        p = pop.plan(prob, k, strategy="stratified")
        ops = pop.build(prob, p)
        batch = (ops, *backends_mod.cold_start(ops))
        fns = {
            name: backends_mod.make_map_solver(
                prob.K_mv, prob.KT_mv, kw,
                name if name == "matvec" else pdhg.fused_structured_engine())
            for name in ("matvec", "fused_structured")
        }
        best, results = _ab_time(fns, batch, repeats)
        for name in fns:
            iters = int(np.asarray(results[name].iterations).sum())
            rows.append(dict(engine=name, k=k, solve_s=best[name],
                             iters=iters))
            emit(f"pop_structured_{name}_k{k}", best[name] * 1e6,
                 f"iters={iters}")
        emit(f"pop_structured_speedup_k{k}", 0.0,
             f"fused_structured_{best['matvec'] / best['fused_structured']:.2f}"
             "x_vs_matvec")
    return rows


def kkt_sweep(k: int = 8, n: int = 150, mi: int = 90, check_every: int = 10,
              budget: int = 1_000, repeats: int = 7, seed: int = 0) -> list:
    """In-loop vs standalone KKT at a fixed iteration budget: the cost of
    convergence checks.  The in-loop path reads the carried half-step
    products (zero extra operator passes); the standalone reference pays 2
    fresh passes per check — at check_every=10 that is ~10% more operator
    applications, all pure overhead.  Same trajectory either way
    (tests/test_engine_conformance.py pins them bit-level)."""
    rng = np.random.default_rng(seed)
    ops = _random_dense_stack(k, n, mi, rng)
    batch = (ops, *backends_mod.cold_start(ops))
    fns = {
        mode: backends_mod.make_map_solver(
            pdhg.dense_K_mv, pdhg.dense_KT_mv,
            dict(max_iters=budget, check_every=check_every,
                 tol_primal=0.0, tol_gap=0.0, kkt=mode), "matvec")
        for mode in ("inloop", "standalone")
    }
    best, _ = _ab_time(fns, batch, repeats)
    saving = 1.0 - best["inloop"] / best["standalone"]
    emit("pop_kkt_inloop", best["inloop"] * 1e6,
         f"standalone_us={best['standalone'] * 1e6:.0f};"
         f"saving={saving * 100:.1f}%;check_every={check_every}")
    return [dict(mode=m, k=k, check_every=check_every, solve_s=t,
                 iters=budget * k) for m, t in best.items()]


def full_engine_sweep(n_demands: int = 30_000, n_jobs: int = 1_000,
                      max_iters: int = 2_000, seed: int = 0) -> list:
    """Paper-scale FULL-problem rows (``--full``): the M-blocked streaming
    engine vs the matvec reference on the unpartitioned baseline — traffic
    at 30k demands and cluster scheduling at 1k jobs, the scale where
    ``engine="auto"`` switches ``solve_full`` onto
    ``fused_structured_full``.  One timed solve per cell at a fixed
    iteration budget (these are minutes-scale solves; min-of-N would just
    repeat the wait), after per-engine compile warmup.  Returns rows
    [{domain, engine, solve_s, iters}, ...]."""
    from repro.problems.traffic_engineering import (TrafficProblem,
                                                    k_shortest_paths,
                                                    make_demands,
                                                    make_topology)
    topo = make_topology(754, 1790, seed=seed)
    pairs, dem = make_demands(topo, n_demands, seed=seed + 1)
    pe = k_shortest_paths(topo, pairs, n_paths=4, max_len=64, seed=seed + 2)
    wl = make_cluster_workload(n_jobs, num_workers=(256, 256, 256),
                               seed=seed)
    cases = {
        "traffic": TrafficProblem(topo, pairs, dem, pe),
        # singleton combos: only the no-space-sharing operator carries the
        # structured metadata the blocked-full engine needs
        "cluster": GavelProblem(wl, space_sharing=False),
    }
    kw = dict(max_iters=max_iters, check_every=200,
              tol_primal=0.0, tol_gap=0.0)
    rows = []
    for domain, prob in cases.items():
        t_by_engine = {}
        for engine in ("matvec", "fused_structured_full"):
            cfg = ExecConfig(engine=engine, solver_kw=kw)
            pop.solve_full_ex(prob, exec_cfg=ExecConfig(
                engine=engine, solver_kw=dict(kw, max_iters=1)))  # warmup
            fr = pop.solve_full_ex(prob, exec_cfg=cfg)
            assert fr.engine == engine, fr.engine
            iters = int(np.asarray(fr.res.iterations).sum())
            t_by_engine[engine] = fr.solve_time_s
            rows.append(dict(domain=domain, engine=engine,
                             solve_s=fr.solve_time_s, iters=iters))
            emit(f"pop_full_{domain}_{engine}", fr.solve_time_s * 1e6,
                 f"iters={iters}")
        emit(f"pop_full_{domain}_speedup", 0.0,
             f"full_{t_by_engine['matvec'] / t_by_engine['fused_structured_full']:.2f}"
             "x_vs_matvec")
    return rows


def run(n_jobs: int = 512, ks=DEFAULT_KS, seed: int = 0,
        backends=DEFAULT_BACKENDS, engines: bool = True) -> dict:
    wl = make_cluster_workload(n_jobs, num_workers=(128, 128, 128), seed=seed)
    prob = GavelProblem(wl, space_sharing=True)
    kw = dict(max_iters=12_000, tol_primal=1e-4, tol_gap=1e-4)
    rows = []
    expos = {}
    # the k=1 baseline is the unpartitioned solve — backend-independent,
    # so run it once and share it across the sweep
    t_full = None
    iters_full = None
    if 1 in ks:
        fr = pop.solve_full_ex(prob, exec_cfg=ExecConfig(solver_kw=kw))
        t_full = fr.solve_time_s
        iters_full = int(fr.res.iterations)
    for backend in backends:
        t1 = None
        for k in ks:
            if k == 1:
                t, iters = t_full, iters_full
            else:
                r = pop.solve_instance(
                    prob, SolveConfig(k=k, strategy="stratified"),
                    ExecConfig(backend=backend, solver_kw=kw))
                t, iters = r.solve_time_s, int(r.iterations.sum())
            rows.append(dict(backend=backend, k=k, solve_s=t, iters=iters))
            t1 = t1 or t
            emit(f"pop_scaling_{backend}_k{k}", t * 1e6,
                 f"speedup={t1/t:.2f}x;iters={iters}")
        # empirical exponent from the k>=2 tail (needs >= 2 points to fit)
        kk = np.array([r["k"] for r in rows
                       if r["backend"] == backend and r["k"] >= 2], float)
        tt = np.array([r["solve_s"] for r in rows
                       if r["backend"] == backend and r["k"] >= 2], float)
        if kk.size >= 2:
            expos[backend] = float(
                np.polyfit(np.log(kk), np.log(t1 / tt), 1)[0])
            emit(f"pop_scaling_exponent_{backend}", 0.0,
                 f"speedup~k^{expos[backend]:.2f}")
        else:
            # None (JSON null), not NaN — json.dump emits a non-standard
            # NaN token that strict parsers reject
            expos[backend] = None
            emit(f"pop_scaling_exponent_{backend}", 0.0,
                 f"skipped: need >=2 ks above 1, got {kk.size}")
    expo = expos[backends[0]]

    # step-engine A/B on dense stacks (fused must never lose to matvec).
    # Deliberately full-size even under run.py --fast: this is the
    # PR-over-PR tracked signal in BENCH_pop.json, so it keeps full k
    # coverage and repeat count (~3 min of the scenario's wall time).
    engine_rows = engine_sweep(ks=ks, seed=seed) if engines else []
    # ... and on REAL structured (Gavel) stacks: fused_structured vs matvec
    # (the ISSUE acceptance signal), plus the in-loop-KKT A/B
    structured_rows = (structured_engine_sweep(ks=tuple(k for k in ks
                                                        if k <= 16),
                                               n_jobs=min(n_jobs, 256),
                                               seed=seed)
                       if engines else [])
    kkt_rows = kkt_sweep(seed=seed) if engines else []

    # solver substrate vs scipy
    rng = np.random.default_rng(0)
    n, mi = 300, 200
    c = rng.normal(size=n)
    G = rng.normal(size=(mi, n))
    h = G @ rng.uniform(0.2, 0.8, n) + rng.uniform(0.1, 1.0, mi)
    with Timer() as t_sp:
        ref = linprog(c, A_ub=G, b_ub=h, bounds=(0, 1), method="highs")
    lp = LinearProgram.build(c=c, G=G, h=h, l=np.zeros(n), u=np.ones(n))
    pdhg.solve_dense(lp, max_iters=100)        # warm the jit cache
    with Timer() as t_us:
        res = pdhg.solve_dense(lp, max_iters=60_000, tol_primal=1e-6,
                               tol_gap=1e-6)
        res.x.block_until_ready()
    gap = abs(float(res.primal_obj) - ref.fun) / (1 + abs(ref.fun))
    emit("pdhg_vs_scipy", t_us.seconds * 1e6,
         f"scipy_us={t_sp.seconds*1e6:.0f};rel_obj_gap={gap:.2e};"
         f"iters={int(res.iterations)}")

    out = {"rows": rows, "exponent": expo, "exponents": expos,
           "engine_rows": engine_rows, "structured_rows": structured_rows,
           "kkt_rows": kkt_rows}
    save_json("pop_scaling", out)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--backend", action="append", default=None,
                    choices=sorted(backends_mod.available_backends()),
                    help="map-step backend to sweep (repeatable; default: "
                         f"{', '.join(DEFAULT_BACKENDS)})")
    ap.add_argument("--n-jobs", type=int, default=512)
    ap.add_argument("--ks", type=int, nargs="+", default=list(DEFAULT_KS))
    ap.add_argument("--engine-sweep", action="store_true",
                    help="run ONLY the step-engine A/B (seconds-scale; "
                         "what `make bench-smoke` uses)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for the engine sweep")
    ap.add_argument("--full", action="store_true",
                    help="run ONLY the paper-scale full-problem rows "
                         "(30k-demand traffic / 1k-job cluster; "
                         "fused_structured_full vs matvec — minutes-scale)")
    args = ap.parse_args(argv)
    if args.full:
        rows = full_engine_sweep()
        save_json("pop_full_engine", {"rows": rows})
        return
    if args.engine_sweep:
        if args.smoke:
            engine_sweep(ks=(1, 2, 4), n=60, mi=40, repeats=2,
                         max_iters=400)
            structured_engine_sweep(ks=(1, 2, 4), n_jobs=48, repeats=2,
                                    max_iters=400)
            kkt_sweep(k=4, n=120, mi=80, budget=600, repeats=3)
        else:
            engine_sweep(ks=tuple(args.ks))
            structured_engine_sweep(ks=tuple(k for k in args.ks if k <= 16))
            kkt_sweep()
        return
    run(n_jobs=args.n_jobs, ks=tuple(args.ks),
        backends=tuple(args.backend or DEFAULT_BACKENDS))


if __name__ == "__main__":
    use_compile_cache()
    main()
