"""The open loop that drives the program through its public entry point,
and the schedule of its arrivals.

The loop offers each tenant's requests at their due times
(``PopSession.step_async`` on a ``PopService`` with the micro-batching
dispatcher) and waits for every one.  Each step is recorded with what the
program reported of it and what it served.
"""

from __future__ import annotations

import faulthandler
import math
import sys
import threading
import time

import numpy as np

from .check import answer_of

DRAIN_S = 60.0          # how long past the window a request may take
STALL_S = 0.5           # submit loop this late: dump every thread's stack


class Tracer:
    """From its own thread, starts the profiler ``after_s`` into the window
    and stops it ``span_s`` later, marking the stretch with the span
    ``bench.traced``, so that the submit loop never waits on the profiler.
    ``probe()`` is read at both ends of the stretch."""

    def __init__(self, jax, log_dir, after_s: float, span_s: float,
                 probe=lambda: None):
        self.jax, self.dir = jax, log_dir
        self.after, self.span = after_s, span_s
        self.probe = probe
        self.state = "idle" if log_dir else "off"
        self.stretch = None            # (host start, host end) of the span
        self.probes = None
        self._thread = None

    def start(self, t0: float) -> None:
        if self.state == "idle":
            self._thread = threading.Thread(target=self._run, args=(t0,),
                                            name="bench-tracer", daemon=True)
            self._thread.start()

    def _run(self, t0: float) -> None:
        time.sleep(max(t0 + self.after - time.perf_counter(), 0.0))
        opts = self.jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        self.jax.profiler.start_trace(self.dir, profiler_options=opts)
        mark = self.jax.profiler.TraceAnnotation("bench.traced")
        mark.__enter__()
        s, p0 = time.perf_counter(), self.probe()
        self.state = "on"
        time.sleep(max(s + self.span - time.perf_counter(), 0.0))
        e, p1 = time.perf_counter(), self.probe()
        mark.__exit__(None, None, None)
        self.jax.profiler.stop_trace()
        self.stretch, self.probes = (s, e), (p0, p1)
        self.state = "done"

    def join(self) -> None:
        if self._thread is not None:
            self._thread.join()


def _record(alloc, inst, domain, **times) -> dict:
    raw = alloc.raw
    lanes = getattr(raw, "iterations", None)
    conv = getattr(raw, "converged", None)
    return dict(
        times, status=alloc.status, faults=tuple(alloc.faults),
        converged=conv is None or bool(np.all(conv)),
        plan_cache=alloc.plan_cache, warm_fraction=alloc.warm_fraction,
        build_time_s=alloc.build_time_s, solve_time_s=alloc.solve_time_s,
        lane_iters=None if lanes is None else int(np.max(lanes)),
        quality=domain.quality(inst, alloc.alloc),
        inst=inst, answer=answer_of(alloc))


def ok(rec: dict) -> bool:
    """A step that served a solve: status ``ok``, no faults, and every
    lane converged (the service reports ``ok`` for a lane that ran out of
    iterations)."""
    return (rec["status"] == "ok" and not rec["faults"]
            and rec.get("converged", False))


def open_schedule(domain, cfg: dict, traffic: dict, seed: int,
                  seconds: float) -> dict:
    """Everything an open loop offers, drawn from the seed: the fleet; the
    set-up's warm groups; ``rate_per_s`` times ``seconds`` arrivals spread
    uniformly over the window (a Poisson process given its count), tenants
    dealt in rounds of one shuffled pass over the fleet, each request its
    tenant's previous instance evolved."""
    rng = np.random.default_rng([seed, 0])
    last = domain.fleet(cfg, seed)
    L = len(last)

    def nxt(t):
        last[t] = domain.evolve(cfg, traffic, last[t], rng)
        return last[t]

    cold = list(last)
    groups = []
    for size in traffic["warm_groups"]:
        members = rng.choice(L, int(size), replace=False)
        groups.append([(int(t), nxt(int(t))) for t in members])
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    due = np.sort(rng.uniform(0.0, seconds, n))
    tenant = np.concatenate([rng.permutation(L)
                             for _ in range(math.ceil(n / L))])[:n]
    reqs = [(float(d), int(t), nxt(int(t))) for d, t in zip(due, tenant)]
    return dict(cold=cold, groups=groups, requests=reqs,
                names=[f"tenant-{i}" for i in range(L)])


def _wait_requests(dispatcher, before: int, futs: list,
                   timeout: float = 120.0) -> None:
    """Until every step of ``futs`` that has not ended waits in the held
    dispatcher."""
    end = time.perf_counter() + timeout
    while (dispatcher.stats()["requests"] - before
           < sum(not f.done() for f, _ in futs)):
        if time.perf_counter() > end:
            raise RuntimeError("warm-up requests never reached the "
                               "dispatcher")
        time.sleep(0.005)
    time.sleep(0.05)


def open_loop(jax, cell, sched: dict, seconds: float, tracer: Tracer,
              clock) -> dict:
    """Set-up steps every tenant cold, all at once, then each group of
    ``warm_groups`` together under the dispatcher's hold, so that every lane
    count the window can launch is compiled; the window offers each request
    at its due time and waits for all of them.  A configuration's
    ``solver_kw`` replaces the domain's registered solver settings."""
    from repro.core.config import ExecConfig
    from repro.service import DispatchConfig, PopService
    to_prog = cell.domain.to_program
    cfg = cell.config
    exec_cfg = (ExecConfig(solver_kw=cfg["solver_kw"])
                if "solver_kw" in cfg else None)
    svc = PopService(dispatch=DispatchConfig())
    sessions = [svc.session(name, to_prog(cfg, inst), exec=exec_cfg)
                for name, inst in zip(sched["names"], sched["cold"])]
    setup = []
    futs = [(sessions[t].step_async(to_prog(cfg, inst)), inst)
            for t, inst in enumerate(sched["cold"])]
    setup += [_record(f.result(), i, cell.domain) for f, i in futs]
    for group in sched["groups"]:
        before = svc.dispatcher.stats()["requests"]
        with svc.dispatcher.hold():
            futs = [(sessions[t].step_async(to_prog(cfg, inst)), inst)
                    for t, inst in group]
            _wait_requests(svc.dispatcher, before, futs)
        setup += [_record(f.result(), i, cell.domain) for f, i in futs]
    programs = [(d, t, inst, to_prog(cfg, inst))
                for d, t, inst in sched["requests"]]
    tracer.probe = svc.dispatcher.stats
    programs0 = clock.programs
    done = {}
    lock = threading.Lock()

    def finished(j):
        def cb(_):
            with lock:
                done[j] = time.perf_counter()
        return cb

    pending, late = [], []
    t0 = time.perf_counter() + 0.01
    tracer.start(t0)
    for j, (d, t, inst, prog) in enumerate(programs):
        due = t0 + d
        faulthandler.dump_traceback_later(
            max(due - time.perf_counter(), 0.0) + STALL_S,
            file=sys.__stderr__)
        time.sleep(max(due - time.perf_counter(), 0.0))
        late.append(time.perf_counter() - due)
        with jax.profiler.TraceAnnotation("bench.submit"):
            f = sessions[t].step_async(prog)
        faulthandler.cancel_dump_traceback_later()
        f.add_done_callback(finished(j))
        pending.append((j, d, inst, f))
    close = t0 + seconds
    records = []
    with jax.profiler.TraceAnnotation("bench.drain"):
        for j, d, inst, f in pending:
            left = close + DRAIN_S - time.perf_counter()
            try:
                a = f.result(timeout=max(left, 0.0))
            except Exception as e:    # noqa: BLE001 -- counted as failed
                records.append(dict(due=t0 + d, end=None, error=repr(e),
                                    status="error", faults=(repr(e),),
                                    inst=inst, answer=None))
                continue
            records.append(_record(a, inst, cell.domain, due=t0 + d,
                                   end=done.get(j, time.perf_counter())))
    tracer.join()
    t1 = max([r["end"] for r in records if r["end"] is not None] + [close])
    return dict(service=svc, setup=setup, records=records, t0=t0, t1=t1,
                give_up=close + DRAIN_S, lateness=np.asarray(late),
                window_programs=clock.programs - programs0,
                stretch=tracer.stretch,
                stretch_dispatch=tracer.probes)
