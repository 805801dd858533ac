#!/usr/bin/env python3
"""One run of one cell of the benchmark on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Reads ``BENCHMARK.json`` beside ``bench/``, finds the cell's configuration,
traffic mix, domain, limits and metric readers by name (``lib/spec.py``),
makes every instance of the run from ``--seed`` in set-up, drives the
program's public entry points (``lib/loops.py``), warms every shape the
window uses, measures for ``--seconds``, then checks what the window served
against the plain reference (``lib/check.py``).

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a stretch of the window is traced and the result carries its
per-layer metrics, the device's busy and traced seconds and a breakdown.
The last line of standard output is the result as one JSON object; the
numbers compared are the last lines of standard error.  Without a TPU, or
with fewer than the cell's chips, the run exits 3 and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import numpy as np  # noqa: E402

from lib import check, device, loops, spec  # noqa: E402
from lib.trace import latest_xplane, reduce_trace  # noqa: E402

# JAX's persistent compilation cache: a fixed path in the checkout, since
# the path is part of what the cache is keyed on
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class CompileClock:
    """Counts the programs JAX has built in this process: ``programs``
    compiled or loaded from the persistent cache, ``hits`` of them loaded."""

    def __init__(self, jax):
        self.programs = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_time)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_time(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def use_compile_cache(jax, path: str = CACHE_DIR) -> str:
    """Keep JAX's persistent compilation cache at ``path``, every program
    in it, whatever ``JAX_COMPILATION_CACHE_DIR`` says."""
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _checked(run: dict, seed: int) -> list:
    """(instance, answer) of the window's steps: every one, where there are
    no more than ``check.SAMPLE``; else a sample drawn from the seed with
    the step of most iterations in it.  Steps that served no solver answer
    are always among them."""
    recs = run["records"]
    done = [j for j, r in enumerate(recs) if r.get("answer") is not None]
    pick = done
    if len(done) > check.SAMPLE:
        rng = np.random.default_rng([seed, 1])
        pick = sorted(rng.choice(done, check.SAMPLE, replace=False).tolist())
        hardest = max(done, key=lambda j: recs[j]["lane_iters"] or 0)
        if hardest not in pick:
            pick.append(hardest)
    pick += [j for j, r in enumerate(recs) if r.get("answer") is None]
    return [(recs[j]["inst"], recs[j].get("answer")) for j in pick]


def _summary(run: dict) -> None:
    recs = run["records"]
    print(f"window: {len(recs)} steps in {run['t1'] - run['t0']:.3f}s, "
          f"programs built in the window: {run['window_programs']}",
          flush=True)
    for r in recs[:: max(len(recs) // 12, 1)]:
        print(f"  step status={r['status']} converged={r.get('converged')} "
              f"plan={r.get('plan_cache')} "
              f"warm_fraction={r.get('warm_fraction')} "
              f"lane_iters={r.get('lane_iters')} "
              f"solve_s={r.get('solve_time_s')} "
              f"build_s={r.get('build_time_s')}", flush=True)
    capped = sum(r["status"] == "ok" and not r["faults"]
                 and not r.get("converged", False) for r in recs)
    print(f"failed: {capped} steps with a lane at the iteration cap, "
          f"{sum(not loops.ok(r) for r in recs) - capped} not ok, with "
          f"faults or never served", flush=True)
    late = run["lateness"]
    print(f"generator lateness: mean {late.mean() * 1e3:.3f} ms, p95 "
          f"{np.percentile(late, 95) * 1e3:.3f} ms, max "
          f"{late.max() * 1e3:.3f} ms over {late.size} requests", flush=True)


def main(argv=None, require_chip: bool = True,
         bench_path: str = os.path.join(ROOT, "BENCHMARK.json"),
         bench_dir: str = BENCH, cache_dir: str = CACHE_DIR) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load(args.workload, bench_path, bench_dir)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise spec.SpecError(f"the program's sources are not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax
    cache = use_compile_cache(jax, cache_dir)
    if require_chip:
        devices = device.require_chips(jax, cell.chips)
    else:
        devices = jax.devices()[: cell.chips]
    print(f"device: {devices[0].device_kind} x{len(devices)}, jax "
          f"{jax.__version__}, compile cache {cache}", flush=True)
    clock = CompileClock(jax)
    tdir = os.path.join(TRACE_DIR, args.workload)
    shutil.rmtree(tdir, ignore_errors=True)
    traffic = cell.traffic
    tracer = loops.Tracer(jax, tdir if args.trace else None,
                          float(traffic["trace_after_s"]),
                          float(traffic["trace_span_s"]))
    cfg, dom = cell.config, cell.domain
    sched = loops.open_schedule(dom, cfg, traffic, args.seed, args.seconds)
    run = loops.open_loop(jax, cell, sched, args.seconds, tracer, clock)
    run["close"] = run["t0"] + args.seconds
    setup_s = run["t0"] - T_START
    print(f"set-up: {setup_s:.3f}s, {len(run['setup'])} steps, "
          f"{clock.programs} programs built, {clock.hits} of them from the "
          f"cache", flush=True)
    _summary(run)

    reduced = None
    if args.trace:
        if tracer.state == "done":
            t_read = time.perf_counter()
            reduced = reduce_trace(latest_xplane(tdir))
            print(f"trace: read in {time.perf_counter() - t_read:.3f}s",
                  flush=True)
        shutil.rmtree(tdir, ignore_errors=True)
        if reduced is None:
            raise RuntimeError("the traced stretch holds no device "
                               "operation; is --seconds past "
                               "trace_after_s + trace_span_s?")
    dev = device.describe(devices, reduced)
    run["service"].close()
    del run["service"]
    gc.collect()

    t_check = time.perf_counter()
    values = check.numbers(dom, cfg, _checked(run, args.seed))
    correct, checks = check.verdict(values, cell.limits)
    print(f"check: {time.perf_counter() - t_check:.3f}s", flush=True)

    ctx = types.SimpleNamespace(setup_s=setup_s, trace=reduced, **run)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.reader(m)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    recs = run["records"]
    result = {"correct": bool(correct), "attempted": len(recs),
              "failed": sum(not loops.ok(r) for r in recs),
              "metrics": metrics, "device": dev}
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except device.NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        sys.exit(3)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
