"""Domain registry + the registry-only MoE placement domain.

The acceptance stakes: all four domains solve through the one session
door with zero domain branches in core/, and MoE placement — onboarded
through the registry alone — lands within 1.5% of its unpartitioned
solve_full objective at k>=4 while beating the greedy baseline."""

import dataclasses

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import ExecConfig, SolveConfig, pop
from repro.core.reduce import coalesce_concat, coalesce_replicated
from repro.domains import (DomainSpec, GavelInstance, SpecProblem,
                           greedy_placement, make_placement_instance,
                           place_experts, register, registry)
from repro.domains.moe_placement import SPEC as MOE_SPEC, _evaluate
from repro.problems.cluster_scheduling import (GavelProblem,
                                               make_cluster_workload)
from repro.problems.traffic_engineering import (TrafficProblem,
                                                k_shortest_paths,
                                                make_demands, make_topology)
from repro.service import PopService

KW = dict(max_iters=250, tol_primal=1e-4, tol_gap=1e-4)


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_builtins_registered(self):
        assert registry.names() == ("gavel", "load_balance", "moe_placement",
                                    "traffic")
        for name in registry.names():
            assert registry.get(name).name == name

    def test_unknown_and_duplicate(self):
        with pytest.raises(KeyError, match="unknown domain"):
            registry.get("warp_drive")
        with pytest.raises(ValueError, match="already registered"):
            register(registry.get("gavel"))
        # replace=True is the sanctioned override (restore right after)
        register(registry.get("gavel"), replace=True)

    def test_spec_for_infers_from_type(self):
        inst = make_placement_instance(16, 4)
        assert registry.spec_for(inst).name == "moe_placement"
        assert registry.spec_for(object()) is None

    def test_declarative_spec_requires_hooks(self):
        with pytest.raises(ValueError, match="missing"):
            DomainSpec(name="hollow")
        # a problem factory alone is a complete spec
        DomainSpec(name="ok", problem=lambda inst: inst)


# ---------------------------------------------------------------------------
# registry-driven == classic pipeline (zero domain branches in core/)
# ---------------------------------------------------------------------------

def test_gavel_registry_matches_classic_pipeline():
    wl = make_cluster_workload(24, seed=0)
    prob = GavelProblem(wl)
    classic = pop.solve_instance(prob,
                                 SolveConfig(k=3, strategy="stratified"),
                                 ExecConfig(solver_kw=KW))
    sess = PopService().session(
        "g", GavelInstance(wl),
        solve=SolveConfig(k=3, strategy="stratified"),
        exec=ExecConfig(solver_kw=KW))
    via_registry = sess.step(GavelInstance(wl))
    assert np.array_equal(classic.alloc, np.asarray(via_registry.alloc))


def test_spec_problem_adapter_shares_matvec_identity():
    """SpecProblem must expose the SPEC's matvecs (one function object per
    domain) so every instance shares the jitted solver caches."""
    a = SpecProblem(MOE_SPEC, make_placement_instance(16, 4, seed=0))
    b = SpecProblem(MOE_SPEC, make_placement_instance(24, 4, seed=1))
    assert a.K_mv is b.K_mv and a.KT_mv is b.KT_mv
    assert a.n_entities == 16 and b.n_entities == 24
    assert a.entity_attrs().shape == (16, 2)
    assert a.entity_scores().shape == (16,)


# ---------------------------------------------------------------------------
# finish: reduce on the host, one read of the result per step
# ---------------------------------------------------------------------------

def _small_instance(domain: str):
    if domain == "moe_placement":
        return make_placement_instance(32, 4, seed=0)
    if domain == "gavel":
        return GavelInstance(make_cluster_workload(24, seed=0))
    topo = make_topology(20, 40, seed=0)
    pairs, dem = make_demands(topo, 24, seed=0)
    return TrafficProblem(topo, pairs, dem,
                          k_shortest_paths(topo, pairs, n_paths=2,
                                           max_len=10, seed=0))


def _launched(domain: str):
    """A prepared k=4 solve of a small instance and its launch's result,
    still on the device."""
    problem = registry.get(domain).make_problem(_small_instance(domain))
    prep = pop.prepare_instance(problem,
                                SolveConfig(k=4, strategy="stratified"),
                                ExecConfig(solver_kw=KW))
    res = pop.solve(problem, prep.plan, prep.ops, backend=prep.backend,
                    engine=prep.engine, solver_kw=prep.solver_kw,
                    backend_opts=prep.opts, warm=prep.warm)
    return prep, res


def _device_slice_reduce(prep, res) -> np.ndarray:
    """Reduce as it read before: each lane of the operator and of
    ``res.x`` sliced on the device and read back one by one."""
    p = prep.plan
    allocs = np.stack([
        np.asarray(prep.problem.extract(
            jax.tree.map(lambda a: a[i], prep.ops), np.asarray(res.x[i]),
            p.idx[i]))
        for i in range(p.k)])
    if p.replication is None:
        return coalesce_concat(allocs, p.idx, p.n_entities)
    return coalesce_replicated(allocs, p.idx, p.replication)


@pytest.mark.parametrize("domain", ["moe_placement", "traffic", "gavel"])
def test_host_reduce_matches_device_slices(domain):
    prep, res = _launched(domain)
    out = pop.finish_prepared(prep, res, 0.0)
    assert np.array_equal(out.alloc, _device_slice_reduce(prep, res))
    assert np.array_equal(out.x, np.asarray(res.x))
    assert np.array_equal(out.y, np.asarray(res.y))
    assert np.array_equal(out.iterations, np.asarray(res.iterations))
    # only the leaves extract reads move, each once for all lanes
    assert out.op_reads < len(jax.tree.leaves(prep.ops))


def test_finish_dispatches_no_device_program(monkeypatch):
    """A MoE step's finish runs no eager device program and reads the
    result only through explicit ``jax.device_get``."""
    from jax._src import core as jax_core
    prep, res = _launched("moe_placement")
    jax.block_until_ready(res)
    dispatched = []
    orig = jax_core.EvalTrace.process_primitive

    def counted(self, prim, tracers, params):
        dispatched.append(prim.name)
        return orig(self, prim, tracers, params)
    monkeypatch.setattr(jax_core.EvalTrace, "process_primitive", counted)

    _device_slice_reduce(prep, res)
    assert dispatched, "the counter sees eager device slices"
    dispatched.clear()
    with jax.transfer_guard_device_to_host("disallow"):
        out = pop.finish_prepared(prep, res, 0.0)
    assert dispatched == []
    assert out.op_reads == 0


def test_host_lanes_move_a_leaf_once_when_read():
    prep, _ = _launched("gavel")
    lanes = pop.HostLanes(prep.ops)
    views = [lanes.lane(i) for i in range(prep.plan.k)]
    S = prep.ops.data[0]
    assert views[1].data[0].shape == S.shape[1:]
    assert views[1].data[0].dtype == S.dtype
    assert lanes.reads == 0
    for i, v in enumerate(views):
        assert np.array_equal(np.asarray(v.data[0]), np.asarray(S[i]))
        assert np.array_equal(v.data[0] * 2, 2 * np.asarray(S[i]))
    assert lanes.reads == 1


@pytest.mark.parametrize("domain", ["moe_placement", "gavel"])
def test_finish_span_counts_operator_reads(domain):
    """MoE's extract reads no operator leaf; Gavel's reads some of them,
    each moved once for all lanes."""
    inst = _small_instance(domain)
    sess = PopService().session(
        "t", inst, solve=SolveConfig(k=4, strategy="stratified"),
        exec=ExecConfig(solver_kw=KW))
    sess.step(inst)
    finish = [r for r in obs.spans()[0]
              if r.name == "pop.finish" and "op_reads" in r.attrs][-1]
    reads = finish.attrs["op_reads"]
    assert reads == 0 if domain == "moe_placement" else reads > 0


# ---------------------------------------------------------------------------
# MoE placement: the acceptance row
# ---------------------------------------------------------------------------

class TestMoEPlacement:
    def test_pop_within_1p5pct_of_full_at_k4(self):
        inst = make_placement_instance(128, 8, seed=0)
        _, _, ev_full = place_experts(inst, solve_cfg=SolveConfig(k=1))
        for k in (4, 8):
            _, res, ev = place_experts(
                inst, solve_cfg=SolveConfig(k=k, strategy="stratified"))
            assert ev["objective"] >= 0.985 * ev_full["objective"], (k, ev)
            assert ev["mem_feasible"]
        assert res.engine == "matvec"       # the domain's preferred engine

    def test_pop_beats_greedy(self):
        inst = make_placement_instance(128, 8, seed=1)
        _, _, ev = place_experts(inst, solve_cfg=SolveConfig(k=4))
        ev_g = _evaluate(inst, greedy_placement(inst))
        assert ev["objective"] > ev_g["objective"]
        # greedy rebalances by moving nearly everything; POP serves the
        # same load while keeping most experts where they are
        assert ev["n_moved"] < 0.5 * ev_g["n_moved"]

    def test_session_warm_chain_with_expert_churn(self):
        svc = PopService()
        inst = make_placement_instance(64, 8, seed=2)
        inst.ids = np.arange(64)
        sess = svc.session("moe", inst, exec=ExecConfig(solver_kw=KW))
        a1 = sess.step(inst)
        assert a1.plan_cache == "miss" and a1.k == 4
        # drift only
        inst2 = dataclasses.replace(inst, load=inst.load * 1.03)
        a2 = sess.step(inst2)
        assert a2.plan_cache == "hit" and a2.warm_fraction == 1.0
        # 6 experts retired, 6 new ones: stable ids keep survivors warm
        keep = np.arange(6, 64)
        rng = np.random.default_rng(3)
        inst3 = dataclasses.replace(
            inst,
            load=np.concatenate([inst2.load[keep],
                                 rng.uniform(1, 4, 6)]),
            mem=np.concatenate([inst.mem[keep], rng.uniform(0.8, 1.2, 6)]),
            current=np.concatenate([a2.alloc[keep],
                                    rng.integers(0, 8, 6)]),
            ids=np.concatenate([inst.ids[keep], 100 + np.arange(6)]))
        a3 = sess.step(inst3)
        assert a3.plan_cache == "repair"
        assert 0.7 < a3.warm_fraction < 1.0

    def test_rounding_respects_memory(self):
        inst = make_placement_instance(48, 6, seed=4)
        inst.cap = np.full(6, 1.3 * inst.mem.sum() / 6)   # tight caps
        placement, _, ev = place_experts(
            inst, solve_cfg=SolveConfig(k=4),
            exec_cfg=ExecConfig(solver_kw=KW))
        assert ev["mem_feasible"]
        assert placement.shape == (48,)
        assert placement.min() >= 0 and placement.max() < 6

    def test_gate_load_feeds_demand_vector(self):
        import jax
        from repro.models.moe import expert_gate_load, init_moe
        rng = jax.random.PRNGKey(0)
        p = init_moe(rng, d=16, d_ff_expert=32, n_experts=8)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
        load = expert_gate_load(p, x, top_k=2)
        assert load.shape == (8,)
        assert load.min() >= 0
        # gate mass is normalised per (token, choice-set): sums to B*S
        np.testing.assert_allclose(load.sum(), 2 * 12, rtol=1e-4)
