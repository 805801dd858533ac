"""Smoke run of the POP allocation service on a TPU.

Drives the service's main path -- ``PopService`` -> ``PopSession.step`` ->
``pop.solve_instance`` -> ``backends.solve_map`` -> ``pdhg.solve_stacked``
-- through the entry points a user calls, with the backend and step engine
that ``"auto"`` resolves to on the chip, and checks what comes out:

  (a) traffic engineering at the paper's scale (section 3.2): 500,000
      demands x 4 paths on the 754-node topology, POP k=64 -- a cold
      step, then a warm step on the same demands x1.05, each held to a
      fixed budget of PDHG iterations (see TE_BUDGET);
  (b) correctness on 3,000 demands: the unpartitioned solve (k=1)
      against HiGHS (scipy) run on the host, and POP k=4 with the
      domain's defaults, cold then warm on demands x1.05, converged and
      within the link capacities;
  (c) one step of every other registered domain (gavel, load_balance,
      moe_placement) at the benchmark suite's default sizes.

Every step must come back with status "ok" and no faults: the service's
degradation ladder keeps users served through a failed solve, and this
script must not pass behind it.  Every step but the budgeted ones of (a)
must also have converged on every lane.

    python3 chip_smoke.py              # one chip: phases (a)-(c)
    python3 chip_smoke.py --chips 4    # only phase (a): shard_map over four
                                       # chips against chunked_vmap on one

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, or without the repository's sources beside it, the script
exits nonzero and prints no such line.  Everything runs in this one
process.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

TE_DEMANDS = 500_000         # paper section 3.2
TE_K = 64
# PDHG iterations per step of (a): the structured engine's ELL gathers run
# as XLA gathers on a TPU, ~3.2 s per iteration of this stack on a v5e, so
# a converged solve (thousands of iterations) does not fit a run
TE_BUDGET = 40
CHECK_DEMANDS = 3_000        # small enough for HiGHS on the host
CHECK_K = 4
WARM_SCALE = 1.05
# a link may be loaded past its capacity by this fraction, and the flow
# served past the demands may total this fraction of all demand (PDHG's
# residual is relative to the norm of all bounds, so a demand of 0.005
# can be exceeded by more than its own size at the domain's tolerance)
FEAS_TOL = 1e-3
HIGHS_RTOL = 1e-3            # full-solve objective vs HiGHS
# the full solve checked against HiGHS runs to a tolerance at which the
# objective is meaningful to HIGHS_RTOL: the traffic domain's default
# 1e-4 duality-gap tolerance leaves the LP objective further off than that
CHECK_SOLVER_KW = dict(max_iters=30_000, tol_primal=1e-6, tol_gap=1e-6)
AGREE_RTOL = 1e-5            # shard_map vs chunked_vmap, same iterations


class SmokeError(Exception):
    """A check failed: the run is not a pass."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def _log(msg: str) -> None:
    print(msg, flush=True)


class _CompileClock:
    """Seconds JAX has spent tracing, lowering and compiling so far."""

    def __init__(self, jax):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration


def _lanes(raw):
    """(iterations, converged) per lane of a step's solver result, or
    (None, None) where the domain reports neither."""
    res = getattr(raw, "res", raw)          # FullResult wraps its solve
    it, conv = (getattr(res, f, None) for f in ("iterations", "converged"))
    if it is None or conv is None:
        return None, None
    import numpy as np
    return np.atleast_1d(np.asarray(it)), np.atleast_1d(np.asarray(conv))


def _step(clock, sess, inst, tag: str, converged: bool = True):
    """One ``session.step``, reported and held to status ok / no faults,
    and, where ``converged``, to every solver lane having converged."""
    c0, t0 = clock.total, time.perf_counter()
    a = sess.step(inst)
    wall = time.perf_counter() - t0
    lanes, conv = _lanes(a.raw)
    lane_max = None if lanes is None else int(lanes.max())
    n_conv = None if conv is None else f"{int(conv.sum())}/{conv.size}"
    m = {k: v for k, v in a.metrics.items()
         if isinstance(v, (int, float)) and not isinstance(v, bool)}
    _log(f"[{tag}] status={a.status} faults={list(a.faults)} "
         f"backend={a.backend} engine={a.engine} kernels={a.kernels} "
         f"k={a.k} iterations={a.iterations} lane_iterations_max={lane_max} "
         f"lanes_converged={n_conv} "
         f"wall_s={wall:.3f} compile_s={clock.total - c0:.3f} "
         f"solve_s={a.solve_time_s:.3f} build_s={a.build_time_s:.3f} "
         f"metrics={json.dumps(m, sort_keys=True)}")
    _check(a.status == "ok" and not a.faults,
           f"{tag}: status {a.status!r}, faults {list(a.faults)}")
    if converged and conv is not None:
        _check(bool(conv.all()), f"{tag}: {int((~conv).sum())} lanes did "
               f"not converge (iterations {lanes[~conv].tolist()})")
    return a


def _traffic_instance(n_demands: int):
    """The paper's WAN: KDL-like 754 nodes / 1,790 links, 4 paths each."""
    from repro.problems.traffic_engineering import (
        TrafficProblem, k_shortest_paths, make_demands, make_topology)
    topo = make_topology(754, seed=0)
    pairs, demand = make_demands(topo, n_demands, seed=1)
    paths = k_shortest_paths(topo, pairs, n_paths=4)
    return TrafficProblem(topo, pairs, demand, paths)


def _scaled(prob, factor: float):
    from repro.problems.traffic_engineering import TrafficProblem
    return TrafficProblem(prob.topo, prob.pairs, prob.demand * factor,
                          prob.path_edges)


def _raw_feasibility(prob, a):
    """(max link utilisation, flow served past the demands as a fraction
    of all demand) of the solver's own allocation."""
    import numpy as np
    f = np.asarray(a.raw.alloc, np.float64)
    util = prob.evaluate(f)["max_edge_util"]
    excess = np.maximum(f.sum(axis=1) - prob.demand, 0.0).sum()
    return util, float(excess / prob.demand.sum())


def _feasible(tag: str, prob, a) -> None:
    util, excess = _raw_feasibility(prob, a)
    _log(f"[{tag}] solver allocation: max_edge_util={util!r} "
         f"demand_excess={excess!r}")
    _check(util <= 1.0 + FEAS_TOL and excess <= FEAS_TOL,
           f"{tag}: the solver's allocation loads a link to {util} of its "
           f"capacity, or serves {excess} of all demand past the demands")


def _budgeted(tag: str, prob, a) -> None:
    """A step of (a): every lane ran the whole budget, nothing diverged,
    and the allocation is finite, non-negative and of the right shape."""
    import numpy as np
    lanes, _ = _lanes(a.raw)
    f = np.asarray(a.raw.alloc)
    util, excess = _raw_feasibility(prob, a)
    _log(f"[{tag}] solver allocation after {TE_BUDGET} iterations: "
         f"max_edge_util={util!r} demand_excess={excess!r}")
    _check(a.k == TE_K and bool((lanes == TE_BUDGET).all()),
           f"{tag}: k={a.k}, lane iterations {sorted(set(lanes.tolist()))}"
           f", not {TE_K} lanes of {TE_BUDGET}")
    _check(a.raw.diverged is None or not np.asarray(a.raw.diverged).any(),
           f"{tag}: lanes diverged")
    _check(f.shape == prob.path_edges.shape[:2]
           and bool(np.isfinite(f).all()) and bool((f >= 0).all()),
           f"{tag}: allocation of shape {f.shape} is not finite and "
           f"non-negative")


def te_instance():
    t0 = time.perf_counter()
    prob = _traffic_instance(TE_DEMANDS)
    _log(f"instance: {TE_DEMANDS} demands x 4 paths, "
         f"{prob.topo.edges.shape[0]} directed links, "
         f"host set-up {time.perf_counter() - t0:.3f}s")
    return prob


def phase_te(clock, svc, prob, backend: str, tag: str):
    """(a): TE at k=64, cold then warm on demands x1.05, each step held to
    TE_BUDGET iterations; the domain's other solver settings as they are."""
    from repro.core import SolveConfig
    from repro.domains.traffic import SPEC
    exec_cfg = dataclasses.replace(
        SPEC.default_exec, backend=backend,
        solver_kw=dict(SPEC.default_exec.solver_kw, max_iters=TE_BUDGET))
    sess = svc.session(tag, prob, solve=SolveConfig(k=TE_K),
                       exec=exec_cfg)
    cold = _step(clock, sess, prob, f"{tag} cold", converged=False)
    _budgeted(f"{tag} cold", prob, cold)
    warm_prob = _scaled(prob, WARM_SCALE)
    warm = _step(clock, sess, warm_prob, f"{tag} warm", converged=False)
    _budgeted(f"{tag} warm", warm_prob, warm)
    _check(warm.plan_cache == "hit" and warm.warm_fraction == 1.0,
           f"{tag}: the warm step did not start from the cold one (plan "
           f"{warm.plan_cache}, warm fraction {warm.warm_fraction})")
    return cold, warm


def _highs_total_flow(prob) -> float:
    """Max total flow of the same LP, built straight from the instance's
    paths and solved by HiGHS: the independent reference for (b)."""
    import numpy as np
    import scipy.sparse as sparse
    from scipy.optimize import linprog
    pe = prob.path_edges                                  # [n, P, L]
    n, P, _ = pe.shape
    E = prob.topo.edges.shape[0]
    var = np.arange(n * P).reshape(n, P)
    j, p, slot = np.nonzero(pe >= 0)
    rows = np.concatenate([np.repeat(np.arange(n), P),
                           n + pe[j, p, slot]])
    cols = np.concatenate([var.ravel(), var[j, p]])
    A = sparse.coo_matrix((np.ones(rows.shape[0]), (rows, cols)),
                          shape=(n + E, n * P)).tocsr()
    b = np.concatenate([prob.demand, prob.topo.capacity])
    has_path = (pe >= 0).any(axis=2).ravel()
    upper = np.where(has_path, np.repeat(prob.demand, P), 0.0)
    res = linprog(-np.ones(n * P), A_ub=A, b_ub=b,
                  bounds=np.stack([np.zeros(n * P), upper], axis=1),
                  method="highs")
    _check(res.status == 0, f"HiGHS failed: {res.message}")
    return float(-res.fun)


def phase_check(clock, svc):
    """(b): the chip's full solve against HiGHS on the host, and POP k=4
    cold then warm with the domain's defaults."""
    from repro.core import ExecConfig, SolveConfig
    prob = _traffic_instance(CHECK_DEMANDS)
    full = _step(clock, svc.session(
        "te-check-full", prob, solve=SolveConfig(k=1),
        exec=ExecConfig(solver_kw=CHECK_SOLVER_KW)), prob, "te-check full")
    _check(full.k == 1, f"te-check full ran at k={full.k}")
    _feasible("te-check full", prob, full)
    sess = svc.session("te-check-pop", prob, solve=SolveConfig(k=CHECK_K))
    cold = _step(clock, sess, prob, "te-check pop cold")
    _feasible("te-check pop cold", prob, cold)
    warm_prob = _scaled(prob, WARM_SCALE)
    warm = _step(clock, sess, warm_prob, "te-check pop warm")
    _feasible("te-check pop warm", warm_prob, warm)
    _check(warm.iterations < cold.iterations,
           f"te-check pop: warm step took {warm.iterations} iterations, "
           f"the cold one {cold.iterations}")
    t0 = time.perf_counter()
    ref = _highs_total_flow(prob)
    got = full.metrics["total_flow"]
    rel = abs(got - ref) / abs(ref)
    _log(f"[te-check] highs_total_flow={ref!r} (host "
         f"{time.perf_counter() - t0:.3f}s) full_total_flow={got!r} "
         f"rel_err={rel:.3e} pop{CHECK_K}_total_flow="
         f"{cold.metrics['total_flow']!r} pop{CHECK_K}_rel_to_highs="
         f"{cold.metrics['total_flow'] / ref!r}")
    _check(rel <= HIGHS_RTOL,
           f"full solve total flow {got} vs HiGHS {ref}: rel {rel:.3e}")


def phase_domains(clock, svc):
    """(c): one step of each other registered domain."""
    from repro.domains import BalanceInstance, GavelInstance
    from repro.domains.moe_placement import make_placement_instance
    from repro.problems.cluster_scheduling import make_cluster_workload
    from repro.problems.load_balancing import make_shard_workload
    wl = make_cluster_workload(448, num_workers=(256, 256, 256), seed=0)
    gavel = GavelInstance(wl, space_sharing=True)
    _step(clock, svc.session("gavel", gavel), gavel, "gavel")
    sw = make_shard_workload(1024, 64, seed=0)
    lb = BalanceInstance(load=sw.load, n_targets=64, current=sw.placement,
                         eps_frac=sw.eps_frac)
    a = _step(clock, svc.session("load_balance", lb), lb, "load_balance")
    # the domain reports no lane convergence; its rounded placement must
    # keep every server inside its load window and memory
    _check(bool(a.raw.feasible), "load_balance: placement infeasible")
    moe = make_placement_instance(512, 16, seed=0)
    _step(clock, svc.session("moe_placement", moe), moe, "moe_placement")


def _agree(tag: str, a, b) -> None:
    import numpy as np
    x = np.asarray(a.raw.alloc, np.float64)
    y = np.asarray(b.raw.alloc, np.float64)
    rel = np.abs(x - y).sum() / max(np.abs(y).sum(), 1e-30)
    fa, fb = a.metrics["total_flow"], b.metrics["total_flow"]
    frel = abs(fa - fb) / abs(fb)
    _log(f"[{tag}] alloc_rel_l1={rel:.3e} total_flow {fa!r} vs "
         f"{fb!r} (rel {frel:.3e})")
    _check(rel <= AGREE_RTOL and frel <= AGREE_RTOL,
           f"{tag}: the four-chip and one-chip allocations disagree "
           f"(rel L1 {rel:.3e}, total flow rel {frel:.3e})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only phase (a), shard_map over four chips "
                         "against chunked_vmap on one")
    args = ap.parse_args(argv)

    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        from benchmarks.common import use_compile_cache
        from repro.service import PopService
    except ImportError as e:
        raise SmokeError(f"the repository's sources are not beside this "
                         f"script ({e})")
    cache = use_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    _check(dev.platform == "tpu",
           f"no TPU: JAX found {len(devices)} {dev.platform} device(s)")
    _check(len(devices) >= args.chips,
           f"--chips {args.chips} needs {args.chips} TPUs, found "
           f"{len(devices)}")
    _log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}, "
         f"compile cache {cache}")
    clock = _CompileClock(jax)
    t0 = time.perf_counter()
    svc = PopService()
    try:
        if args.chips == 4:
            prob = te_instance()
            sharded = phase_te(clock, svc, prob, "auto", "te-4chip")
            _check(sharded[0].backend == "shard_map",
                   f"auto resolved to {sharded[0].backend} on four chips")
            # what one chip runs this stack as: plain vmap of all 64
            # lanes compiles to 12.5 GB for a v5e's 16 GB, chunks of 16
            # to 6.8 GB (see PERF.md)
            one = phase_te(clock, svc, prob, "chunked_vmap", "te-1chip")
            _agree("cold shard_map vs chunked_vmap", sharded[0], one[0])
            _agree("warm shard_map vs chunked_vmap", sharded[1], one[1])
        else:
            phase_te(clock, svc, te_instance(), "auto", "te")
            _log(f"elapsed {time.perf_counter() - t0:.3f}s")
            phase_check(clock, svc)
            _log(f"elapsed {time.perf_counter() - t0:.3f}s")
            phase_domains(clock, svc)
    finally:
        svc.close()
    _log(f"total {time.perf_counter() - t0:.3f}s, of which compile "
         f"{clock.total:.3f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
