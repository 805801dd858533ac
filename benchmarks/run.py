"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the repo contract and persists
JSON artifacts to experiments/bench/.

    PYTHONPATH=src python -m benchmarks.run [--fast]

``--emit PATH`` additionally writes ONE machine-readable perf snapshot
(scenario -> wall-clock + the scenario's result payload, plus platform
metadata) so the perf trajectory is tracked PR-over-PR:

    PYTHONPATH=src python -m benchmarks.run --fast --emit BENCH_pop.json

The committed ``BENCH_pop.json`` at the repo root is the ``--fast``
snapshot — regenerate it with exactly that command when solver or backend
changes move the numbers.

``--check BASELINE`` compares the CURRENT run against a committed snapshot
and exits nonzero on regression (``make bench-check``):

    PYTHONPATH=src python -m benchmarks.run --fast --check BENCH_pop.json

A scenario regresses when it errors while the baseline succeeded, or when
its wall-clock exceeds ``--check-tol`` (default 2.5x) times the baseline
AND is more than 5s slower in absolute terms (small scenarios are all
jit-compile noise).  Scenarios absent from the baseline are reported as
NEW, not failed, so adding a benchmark does not break the gate.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback


def _meta(fast: bool) -> dict:
    import jax
    return {
        "fast": fast,
        "platform": jax.default_backend(),
        "device_count": jax.device_count(),
        "jax_version": jax.__version__,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="reduced sizes (CI smoke)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--emit", default=None, metavar="PATH",
                    help="write a machine-readable perf snapshot JSON "
                         "(scenario wall-clock + payloads + platform)")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="compare this run against a committed snapshot "
                         "(e.g. BENCH_pop.json); exit nonzero on regression")
    ap.add_argument("--check-tol", type=float, default=2.5,
                    help="wall-clock regression tolerance ratio for --check")
    args = ap.parse_args()

    from . import (bench_churn, bench_cluster_scheduling,
                   bench_load_balancing, bench_moe_placement,
                   bench_online_resolve, bench_pop_scaling,
                   bench_replication, bench_serve_scale, bench_session,
                   bench_skewed_splits, bench_traffic_engineering,
                   bench_tuning)

    suite = {
        # paper Fig. 3
        "cluster_scheduling": lambda: bench_cluster_scheduling.run(
            n_jobs=128 if args.fast else 448),
        # paper Fig. 4
        "traffic_engineering": lambda: bench_traffic_engineering.run(
            n_demands=3_000 if args.fast else 20_000),
        # paper Fig. 5
        "load_balancing": lambda: bench_load_balancing.run(
            n_shards=256 if args.fast else 1024,
            n_servers=16 if args.fast else 64),
        # paper Fig. 6
        "skewed_splits": lambda: bench_skewed_splits.run(
            n_demands=2_000 if args.fast else 10_000),
        # paper §4.3
        "replication": lambda: bench_replication.run(),
        # paper §2.4 + solver substrate (backend AND step-engine sweeps)
        "pop_scaling": lambda: bench_pop_scaling.run(
            n_jobs=128 if args.fast else 512),
        # online setting: warm-started re-solves on perturbed instances
        "online_resolve": lambda: bench_online_resolve.run(fast=args.fast),
        # churn-aware warm starts across partition changes (PopPlan layer)
        "churn": lambda: bench_churn.run(fast=args.fast),
        # the fourth scenario: MoE expert placement (registry-onboarded)
        "moe_placement": lambda: bench_moe_placement.run(
            n_experts=128 if args.fast else 512,
            n_devices=8 if args.fast else 16),
        # multi-tenant PopService session throughput (plan-cache hit rate,
        # warm fraction, steps/sec under interleaved tenants)
        "session": lambda: bench_session.run(fast=args.fast),
        # fleet scale: 10k tenants (1k fast) through the micro-batched
        # dispatcher — batching ratio, paged-cache hit rate, p50/p99
        "serve_scale": lambda: bench_serve_scale.run(fast=args.fast),
        # SLO auto-tuner: measured-curve config picks vs the static
        # default — steps/sec + realized quality at a fixed 2% SLO
        "tuning": lambda: bench_tuning.run(fast=args.fast),
    }
    if args.only:
        keep = set(args.only.split(","))
        suite = {k: v for k, v in suite.items() if k in keep}

    print("name,us_per_call,derived")
    failures = 0
    snapshot = {"meta": _meta(args.fast), "scenarios": {}}
    for name, fn in suite.items():
        t0 = time.perf_counter()
        try:
            payload = fn()
            wall = time.perf_counter() - t0
            snapshot["scenarios"][name] = {
                "wall_s": round(wall, 3),
                "result": payload if isinstance(payload, dict) else None,
            }
            print(f"# {name}: done in {wall:.1f}s",
                  file=sys.stderr, flush=True)
        except Exception:                                   # noqa: BLE001
            failures += 1
            snapshot["scenarios"][name] = {
                "wall_s": round(time.perf_counter() - t0, 3),
                "error": traceback.format_exc(limit=3),
            }
            print(f"# {name}: FAILED\n{traceback.format_exc()}",
                  file=sys.stderr, flush=True)
    if args.emit:
        # NaN/Infinity -> null: strict JSON parsers reject the bare tokens
        clean = json.loads(json.dumps(snapshot, default=str),
                           parse_constant=lambda _: None)
        with open(args.emit, "w") as f:
            json.dump(clean, f, indent=1)
        print(f"# snapshot -> {args.emit}", file=sys.stderr, flush=True)
    if args.check:
        failures += _check_against_baseline(snapshot, args.check,
                                            args.check_tol,
                                            subset=bool(args.only))
    if failures:
        raise SystemExit(1)


def _check_against_baseline(snapshot: dict, baseline_path: str,
                            tol: float, subset: bool = False) -> int:
    """Compare the fresh ``snapshot`` against a committed baseline.  A
    scenario regresses when it now errors (baseline succeeded) or when it
    is both ``tol``x and >5s slower than the baseline; returns the
    regression count and prints a verdict line per scenario."""
    with open(baseline_path) as f:
        baseline = json.load(f)
    base_sc = baseline.get("scenarios", {})
    meta = baseline.get("meta", {})
    cur_meta = snapshot["meta"]
    if (meta.get("platform") != cur_meta["platform"]
            or meta.get("fast") != cur_meta["fast"]):
        print(f"# check: baseline meta {meta} != current "
              f"{{'platform': {cur_meta['platform']!r}, "
              f"'fast': {cur_meta['fast']!r}}} — wall-clock comparison "
              "may be meaningless", file=sys.stderr, flush=True)
    regressions = 0
    for name, cur in snapshot["scenarios"].items():
        base = base_sc.get(name)
        if base is None:
            print(f"# check {name}: NEW (not in baseline)",
                  file=sys.stderr, flush=True)
            continue
        if "error" in cur and "error" not in base:
            print(f"# check {name}: REGRESSION (now fails, baseline passed)",
                  file=sys.stderr, flush=True)
            regressions += 1
            continue
        if "error" in base:
            # equally broken (or newly fixed) — wall-clock is meaningless
            verdict = "ok (fixed)" if "error" not in cur \
                else "ok (still failing in baseline too)"
            print(f"# check {name}: {verdict}", file=sys.stderr, flush=True)
            continue
        ratio = cur["wall_s"] / max(base["wall_s"], 1e-9)
        slow = (ratio > tol and cur["wall_s"] - base["wall_s"] > 5.0)
        verdict = "REGRESSION" if slow else "ok"
        print(f"# check {name}: {verdict} "
              f"({base['wall_s']:.1f}s -> {cur['wall_s']:.1f}s, "
              f"{ratio:.2f}x)", file=sys.stderr, flush=True)
        regressions += int(slow)
    if not subset:                   # --only deliberately runs a subset
        for name in base_sc:
            if name not in snapshot["scenarios"]:
                print(f"# check {name}: MISSING from current run — "
                      "REGRESSION", file=sys.stderr, flush=True)
                regressions += 1
    return regressions


if __name__ == "__main__":
    from .common import use_compile_cache
    use_compile_cache()
    main()
