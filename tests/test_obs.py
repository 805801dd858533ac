"""The span log of the serving path (``repro.obs``): a request-id span tree
from ``step_async`` to the served allocation, the dispatcher's spans and
counters, the bounded ring, and the spans' place in a profiler trace.

Four small MoE-placement tenants step through a dispatching
``PopService``: a cold round and a warm round, each submitted under the
dispatcher's ``hold()`` so that their launches coalesce, then one direct
``step`` per tenant."""

import dataclasses
import glob
import os
import time

import jax
import pytest

from repro import obs
from repro.core import ExecConfig
from repro.domains.moe_placement import make_placement_instance
from repro.service import DispatchConfig, PopService

KW = dict(max_iters=200, tol_primal=1e-4, tol_gap=1e-4)
TENANTS = [f"obs-{i}" for i in range(4)]
STAGES = ("pop.pool_wait", "pop.lock_wait", "pop.prepare", "pop.dispatch",
          "pop.finish")


def _instances(drift: float):
    out = []
    for i in range(len(TENANTS)):
        inst = make_placement_instance(64, 8, seed=i)
        out.append(dataclasses.replace(inst, load=inst.load * drift))
    return out


def _held_round(svc, sessions, insts) -> list:
    """Submit one step per tenant while the dispatcher is held, release it
    once every ticket waits in its queue, and return the allocations."""
    before = svc.dispatcher.stats()["requests"]
    with svc.dispatcher.hold():
        futs = [s.step_async(i) for s, i in zip(sessions, insts)]
        end = time.perf_counter() + 120.0
        while svc.dispatcher.stats()["requests"] - before < len(futs):
            assert time.perf_counter() < end, "tickets never queued"
            time.sleep(0.005)
        time.sleep(0.05)     # a ticket is counted just before its put
    return [f.result(timeout=300) for f in futs]


def _steps(svc, rounds):
    sessions = [svc.session(t, i, exec=ExecConfig(solver_kw=KW))
                for t, i in zip(TENANTS, _instances(1.0))]
    allocs = []
    for drift in rounds:
        allocs += _held_round(svc, sessions, _instances(drift))
    return sessions, allocs


@pytest.fixture(scope="module")
def run():
    """``(spans of this run's requests, dispatcher stats, root spans)``."""
    t_start = time.perf_counter_ns()
    svc = PopService(dispatch=DispatchConfig())
    try:
        sessions, allocs = _steps(svc, (1.0, 1.02))
        allocs += [s.step(i) for s, i in zip(sessions, _instances(1.04))]
        assert all(a.status == "ok" for a in allocs)
        stats = svc.dispatcher.stats()
    finally:
        svc.close()
    recs, _ = obs.spans()
    roots = [s for s in recs if s.name == "pop.step"
             and s.attrs.get("tenant") in TENANTS and s.t0_ns >= t_start]
    assert len(roots) == 3 * len(TENANTS)
    rids = {s.rid for s in roots}
    mine = [s for s in recs if s.rid in rids
            or set(s.attrs.get("rids", ())) & rids]
    # pop.device of a coalesced launch runs on the dispatcher thread under
    # its pop.launch, which serves no single request
    launch_ids = {s.id for s in mine if s.name == "pop.launch"}
    mine += [s for s in recs if s.parent in launch_ids and s not in mine]
    return mine, stats, roots


def test_every_request_has_one_root(run):
    mine, _, roots = run
    rids = [s.rid for s in roots]
    assert len(set(rids)) == len(rids)
    for r in roots:
        assert r.parent is None
        assert r.attrs["status"] == "ok"
        assert r.attrs["plan"] in ("miss", "hit")
        waits = [s for s in mine
                 if s.rid == r.rid and s.name == "pop.pool_wait"]
        # step_async records its pool wait; a direct step() has none
        assert len(waits) <= 1
    assert sum(s.name == "pop.pool_wait" for s in mine) == 2 * len(TENANTS)


def test_children_lie_inside_their_parents(run):
    mine, _, _ = run
    by_id = {s.id: s for s in mine}
    children = [s for s in mine if s.parent is not None]
    assert {s.name for s in children} >= {
        "pop.pool_wait", "pop.lock_wait", "pop.prepare", "pop.plan",
        "pop.build", "pop.remap", "pop.dispatch", "pop.batch", "pop.queue",
        "pop.device", "pop.finish"}
    for s in children:
        p = by_id[s.parent]
        assert s.id > p.id and s.rid == p.rid
        assert p.t0_ns <= s.t0_ns <= s.t1_ns <= p.t1_ns, (s, p)


def test_stage_spans_take_cpu_time(run):
    mine, _, _ = run
    timed = {s.name for s in mine if s.cpu_ns is not None}
    assert timed == {"pop.prepare", "pop.launch", "pop.finish"}
    assert all(s.cpu_ns >= 0 for s in mine if s.cpu_ns is not None)


def test_launches_name_every_request(run):
    mine, _, roots = run
    queues = {s.rid: s for s in mine if s.name == "pop.queue"}
    launches = [s for s in mine if s.name == "pop.launch"]
    served = sorted(rid for s in launches for rid in s.attrs["rids"])
    assert served == sorted(r.rid for r in roots)
    for s in launches:
        group = s.attrs["group"]
        assert group == len(s.attrs["rids"])
        assert s.rid == (s.attrs["rids"][0] if group == 1 else None)
        assert s.attrs["lanes"] >= 4 * group
        for rid in s.attrs["rids"]:
            assert queues[rid].t1_ns <= s.t0_ns
    # each held round coalesced its tickets into a shared launch
    order = sorted(roots, key=lambda s: s.t0_ns)
    for rnd in range(2):
        rids = {s.rid for s in order[rnd * 4:(rnd + 1) * 4]}
        assert any(s.attrs["group"] > 1 and set(s.attrs["rids"]) <= rids
                   for s in launches)


def test_stages_cover_the_step(run):
    """The stage spans cover 90% of the steps' time.  A pause of the
    interpreter (a collection, a loaded machine) can fall between two
    stages of one step, so each step is held to half and the run's total
    to 90%."""
    mine, _, roots = run
    covered_all = 0
    for r in roots:
        kids = sorted((s.t0_ns, s.t1_ns) for s in mine
                      if s.parent == r.id and s.name in STAGES)
        covered, end = 0, r.t0_ns
        for a, b in kids:
            covered += max(b - max(a, end), 0)
            end = max(end, b)
        assert covered >= 0.5 * r.dur_ns, (r, covered / r.dur_ns)
        covered_all += covered
    assert covered_all >= 0.9 * sum(r.dur_ns for r in roots)


@pytest.mark.parametrize("counter, name", [("queue_wait_s", "pop.queue"),
                                           ("device_wait_s", "pop.device")])
def test_counters_total_their_spans(run, counter, name):
    mine, stats, _ = run
    total = sum(s.dur_ns for s in mine if s.name == name) / 1e9
    assert total > 0
    assert stats[counter] == pytest.approx(total, rel=1e-9)


def test_ring_reports_wrapping():
    for _ in range(obs.RING_SIZE + 1):
        with obs.span("test.fill"):
            pass
    recs, wrapped = obs.spans()
    assert wrapped and len(recs) == obs.RING_SIZE
    fill = [s for s in recs if s.name == "test.fill"]
    assert len(fill) >= obs.RING_SIZE - 100
    assert all(a.t1_ns <= b.t1_ns for a, b in zip(fill, fill[1:]))


def test_spans_land_in_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData
    svc = PopService(dispatch=DispatchConfig())
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    try:
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            _steps(svc, (1.0,))
        finally:
            jax.profiler.stop_trace()
    finally:
        svc.close()
    [path] = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                       recursive=True)
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            names |= {e.name for line in plane.lines for e in line.events}
    assert {"pop.step", "pop.prepare", "pop.launch"} <= names
