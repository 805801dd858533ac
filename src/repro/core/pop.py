"""POP orchestrator: a staged pipeline around the :class:`PopPlan` artifact.

The paper's technique as a composable module.  A domain problem (cluster
scheduling, traffic engineering, load balancing, MoE expert placement, ...)
subclasses :class:`POPProblem`; the pipeline then runs in four separable
stages:

  ``plan()``    partition entities into k self-similar subsets
                (``core/partition.py``), optionally replicating hot entities
                (``core/replicate.py``), and record the per-entity ->
                (lane, slot) provenance plus the problem's sub-LP layout in
                a reusable :class:`~repro.core.plan.PopPlan`.
  ``build()``   materialise k identically-shaped sub-LPs from the plan and
                STACK them on a leading axis (fills ``plan.shapes``).
  ``solve()``   one batched PDHG solve through a pluggable execution
                backend (``core/backends.py``: serial / vmap / chunked_vmap
                / shard_map / pmap — sub-problems are independent, so the
                map step needs ZERO collectives; this is the whole point of
                POP).
  ``reduce()``  coalesce sub-allocations back to global entity order
                (``core/reduce.py``), on the host: one read of the
                result, the operator through :class:`HostLanes`.

:func:`solve_instance` is the one-call wrapper chaining all four,
configured by the frozen dataclasses in ``core/config.py``
(:class:`SolveConfig` / :class:`ExecConfig`); the legacy kwarg surface
:func:`pop_solve` forwards onto it with a DeprecationWarning.  These
stages are the DOCUMENTED INTERNALS that the public surface drives: the
domain registry (``repro.domains``) describes each scenario
declaratively, and :class:`repro.service.PopService` sessions call
:func:`solve_instance` per online step.  Online callers hold onto the
:class:`PopPlan` (every :class:`POPResult` carries its plan) and re-plan
only when they must — planning is pure numpy and cheap, but *re-using* a
plan is what keeps warm starts exact and the jit caches hot.

Warm starts across churn
------------------------

``pop_solve(warm=prev)`` re-solves an updated instance from a previous
:class:`POPResult`:

* **identity churn** (same entities, same k): the previous plan is reused
  verbatim and every lane continues from its previous (x, y) iterates —
  bit-for-bit the PR-2 warm path.
* **anything else** (entity arrivals/departures, k changes,
  re-stratification via ``replan=True`` or an explicit ``plan=``):
  :func:`~repro.core.plan.remap_warm` scatters the old per-entity iterates
  onto the new plan's lanes, gives freshly arrived entities a dual-only
  warm start, and marks lanes with no matched entity to start cold via a
  per-lane mask (``backends._resolve_warm`` applies it with a ``jnp.where``
  — no Python-level branch).  Pass ``entity_ids=`` stable external ids when
  positional indexing churns (a scheduler's job ids, a balancer's group
  ids); without them entities are matched by position.

``benchmarks/bench_churn.py`` measures the warm/cold iteration ratio under
5/20/50% entity churn for all three paper domains.

``solve_full`` runs the unpartitioned baseline (k=1 path) for quality
comparison — the paper's "original problem formulation" — through the same
backend/engine substrate as the POP path.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from . import backends as backends_mod
from . import partition as part_mod
from . import pdhg
from .config import ExecConfig, SolveConfig
from .pdhg import OperatorLP, SolveResult
from .plan import PopPlan, SubLayout, WarmStart, remap_warm, repair_plan
from .replicate import ReplicationPlan, plan_replication, replicated_partition
from .reduce import coalesce_concat, coalesce_replicated


class POPProblem:
    """Interface a domain problem implements to be POP-able.

    Subclasses define how to build the full LP and any entity-subset sub-LP
    (in operator form), how to pull the per-entity allocation out of the LP
    solution vector, and how to score an allocation.
    """

    n_entities: int

    # --- partitioning inputs -------------------------------------------------
    def entity_attrs(self) -> np.ndarray:
        """[n, d] attribute vectors (for similarity + stratification)."""
        raise NotImplementedError

    def entity_scores(self) -> np.ndarray:
        """[n] scalar load/demand (stratification + replication)."""
        attrs = self.entity_attrs()
        return attrs[:, 0] if attrs.ndim == 2 else attrs

    # --- LP construction ------------------------------------------------------
    def build_sub(self, idx_row: np.ndarray, frac: float,
                  scale: Optional[np.ndarray] = None) -> OperatorLP:
        """Sub-LP over entities ``idx_row`` (-1 = padded slot) with ``frac``
        of every resource.  ``scale`` (replication) multiplies per-entity
        demand.  MUST return identical array shapes for identical row
        lengths, so sub-problems stack."""
        raise NotImplementedError

    def build_full(self) -> OperatorLP:
        return self.build_sub(np.arange(self.n_entities), 1.0)

    def sub_layout(self, n_slots: int) -> Optional[SubLayout]:
        """Describe the sub-LP variable/row layout for warm-start remapping
        (see ``core/plan.py``).  ``None`` (the default) disables cross-plan
        warm starts — ``pop_solve(warm=)`` then degrades to cold instead of
        raising when the partition changed."""
        return None

    # operator matvecs — override for structured (non-dense) constraints
    K_mv = staticmethod(pdhg.dense_K_mv)
    KT_mv = staticmethod(pdhg.dense_KT_mv)

    # --- solution handling -----------------------------------------------------
    def extract(self, op: OperatorLP, x: np.ndarray,
                idx_row: np.ndarray) -> np.ndarray:
        """Per-slot allocation rows [n_per, ...] from an LP solution.
        ``x`` is the lane's numpy row; ``op`` is the lane's operator as
        :meth:`HostLanes.lane` views, which move to the host only when
        read."""
        raise NotImplementedError

    def evaluate(self, alloc: np.ndarray) -> dict:
        raise NotImplementedError


@dataclasses.dataclass
class POPResult:
    alloc: np.ndarray
    idx: np.ndarray
    solve_time_s: float
    build_time_s: float
    iterations: np.ndarray
    converged: np.ndarray
    similarity: dict
    sub_objectives: np.ndarray
    replication: Optional[ReplicationPlan] = None
    # raw stacked solver iterates [k, n_var]/[k, n_con] — the warm-start
    # state for online re-solves (``pop_solve(..., warm=prev_result)``)
    x: Optional[np.ndarray] = None
    y: Optional[np.ndarray] = None
    # the plan this result was computed under (reused/remapped by warm
    # re-solves) and, for warm solves, the remap statistics
    plan: Optional[PopPlan] = None
    warm_stats: Optional[dict] = None
    # observability: the backend/engine that ACTUALLY ran ("auto" resolved
    # — callers and benchmarks otherwise can't see what won), and where
    # the plan came from: "reused" (cache hit), "repaired" (incremental
    # re-plan under churn), "fresh" (new partition), "provided" (explicit
    # plan=)
    backend: Optional[str] = None
    engine: Optional[str] = None
    kernels: Optional[str] = None     # "pallas" | "interpret" | "xla"
    plan_source: Optional[str] = None
    # [k] per-lane divergence-quarantine flags from the solver (lanes whose
    # KKT score went non-finite or blew up; see pdhg.solve_stacked) — what
    # the service layer reads to cold-restart only the poisoned lanes
    diverged: Optional[np.ndarray] = None
    # stacked operator leaves reduce moved to the host for ``extract``
    # (the ``pop.finish`` span's ``op_reads``)
    op_reads: int = 0


# --------------------------------------------------------------------------
# map-step backends — the execution substrate lives in ``core/backends.py``;
# this alias keeps the historical ``pop.MAP_BACKENDS`` surface working
# --------------------------------------------------------------------------

MAP_BACKENDS = backends_mod.MAP_BACKENDS


# --------------------------------------------------------------------------
# stage 1: plan
# --------------------------------------------------------------------------

def plan(
    problem: POPProblem,
    k: int,
    *,
    strategy: str = "random",
    seed: int = 0,
    replicate_threshold: Optional[float] = None,
    partition_idx: Optional[np.ndarray] = None,
    entity_ids: Optional[np.ndarray] = None,
) -> PopPlan:
    """Partition (+ optionally replicate) ``problem`` into k subsets and
    return the reusable :class:`PopPlan`.  ``strategy`` ∈ {random,
    stratified, stratified_multidim}; an explicit ``partition_idx``
    overrides it (custom or adversarial splits).  ``replicate_threshold``
    enables §4.3 hot-entity replication.  ``entity_ids`` attaches stable
    external ids used to match entities across instances when warm-starting
    through churn."""
    n = problem.n_entities
    scores = np.asarray(problem.entity_scores(), np.float64)
    attrs = np.asarray(problem.entity_attrs(), np.float64)
    if attrs.ndim == 1:
        attrs = attrs[:, None]

    rep = None
    if partition_idx is not None:
        idx = np.asarray(partition_idx)
    elif replicate_threshold is not None:
        rep = plan_replication(scores, k, replicate_threshold)
        idx = replicated_partition(rep, scores, k, seed)
    else:
        idx = part_mod.make_partition(strategy, attrs, scores, n, k, seed)

    entity_of_slot = idx if rep is None else rep.entity_of(idx)
    # similarity diagnostics run on ORIGINAL entity ids
    sim = part_mod.similarity_report(attrs, entity_of_slot)
    layout = problem.sub_layout(idx.shape[1])
    if entity_ids is not None:
        entity_ids = np.asarray(entity_ids)
        if entity_ids.shape[0] != n:
            raise ValueError(f"entity_ids has {entity_ids.shape[0]} entries "
                             f"for {n} entities")
    return PopPlan(k=k, n_entities=n, idx=idx,
                   entity_of_slot=entity_of_slot, strategy=strategy,
                   seed=seed, replication=rep, entity_ids=entity_ids,
                   similarity=sim, layout=layout)


make_plan = plan     # alias: lets ``pop_solve(plan=...)`` shadow the name


# --------------------------------------------------------------------------
# stage 2: build
# --------------------------------------------------------------------------

def build(problem: POPProblem, pop_plan: PopPlan) -> OperatorLP:
    """Materialise the plan's k identically-shaped sub-LPs and stack them
    (``pdhg.stack_ops`` pads per-lane ELL widths to the stack maximum when
    the problem attaches :class:`~repro.core.pdhg.StructuredOperator`
    metadata).  Records the stacked shapes on the plan (what sizes warm
    remaps; ``"ell"`` carries the structured row/col widths so plan
    consumers can see when a rebuild changed the stacked kernel shapes)."""
    subs = []
    for i in range(pop_plan.k):
        subs.append(problem.build_sub(pop_plan.entity_of_slot[i],
                                      1.0 / pop_plan.k,
                                      scale=pop_plan.row_scale(i)))
    ops = pdhg.stack_ops(subs)
    pop_plan.shapes = {"x": tuple(ops.c.shape), "y": tuple(ops.q.shape)}
    if ops.structured is not None:
        s = ops.structured
        # every data-dependent ELL dim: narrow widths, wide-bucket widths
        # AND wide-bucket counts — any of them moving retraces the solve
        pop_plan.shapes["ell"] = (
            int(s.row_idx.shape[-2]), int(s.wrow_idx.shape[-2]),
            int(s.wrow_ids.shape[-1]),
            int(s.col_idx.shape[-2]), int(s.wcol_idx.shape[-2]),
            int(s.wcol_ids.shape[-1]))
    return ops


# --------------------------------------------------------------------------
# stage 3: solve (the map step)
# --------------------------------------------------------------------------

def solve(
    problem: POPProblem,
    pop_plan: PopPlan,
    ops: OperatorLP,
    *,
    backend: str = "auto",
    engine: str = "auto",
    solver_kw: Optional[dict] = None,
    backend_opts: Optional[dict] = None,
    warm=None,
) -> SolveResult:
    """Batched solve of the stacked sub-LPs through ``backends.solve_map``.
    ``warm`` is a :class:`~repro.core.plan.WarmStart` (masked, from
    ``remap_warm``), an (x, y) pair, or a SolveResult-like object."""
    res = backends_mod.solve_map(ops, problem.K_mv, problem.KT_mv,
                                 dict(solver_kw or {}), backend=backend,
                                 engine=engine, warm=warm,
                                 **(backend_opts or {}))
    jax.block_until_ready(res.x)
    return res


# --------------------------------------------------------------------------
# stage 4: reduce
# --------------------------------------------------------------------------

class HostLanes:
    """Per-lane host views of a stacked operator, for ``extract``.

    :meth:`lane` has the operator's structure with every stacked leaf
    replaced by a view of its lane.  A view's ``shape``, ``dtype`` and
    ``ndim`` read no data; the first read of its values (``np.asarray``,
    indexing, arithmetic) moves the whole stacked leaf to the host with
    one ``jax.device_get``, for all k lanes.  A leaf that ``extract``
    never reads stays on the device.  ``reads`` counts the leaves
    moved."""

    def __init__(self, stacked):
        self._leaves, self._treedef = jax.tree.flatten(stacked)
        self._host: dict = {}

    @property
    def reads(self) -> int:
        return len(self._host)

    def _leaf(self, j: int) -> np.ndarray:
        if j not in self._host:
            self._host[j] = jax.device_get(self._leaves[j])
        return self._host[j]

    def lane(self, i: int):
        return self._treedef.unflatten(
            [_LaneView(self, j, i) for j in range(len(self._leaves))])


class _LaneView(np.lib.mixins.NDArrayOperatorsMixin):
    """Lane ``i`` of stacked leaf ``j`` of a :class:`HostLanes`."""

    __slots__ = ("_lanes", "_j", "_i", "shape", "dtype")

    def __init__(self, lanes: HostLanes, j: int, i: int):
        leaf = lanes._leaves[j]
        self._lanes, self._j, self._i = lanes, j, i
        self.shape = tuple(leaf.shape[1:])
        self.dtype = np.dtype(leaf.dtype)

    @property
    def ndim(self) -> int:
        return len(self.shape)

    def __len__(self) -> int:
        return self.shape[0]

    def _value(self) -> np.ndarray:
        return self._lanes._leaf(self._j)[self._i]

    def __array__(self, dtype=None, copy=None):
        return np.array(self._value(), dtype=dtype, copy=copy)

    def __array_ufunc__(self, ufunc, method, *inputs, **kw):
        inputs = tuple(np.asarray(a) if isinstance(a, _LaneView) else a
                       for a in inputs)
        return getattr(ufunc, method)(*inputs, **kw)

    def __getitem__(self, key):
        return self._value()[key]


def reduce(problem: POPProblem, pop_plan: PopPlan, ops, res: SolveResult
           ) -> np.ndarray:
    """Coalesce per-lane allocations into the global one (scatter by entity
    id; replicated entities SUM their replica sub-allocations).  Runs on
    the host and dispatches no device program: ``res.x`` is read once
    (a no-op when it is already on the host), lanes are numpy rows, and
    ``extract`` sees the stacked operator ``ops`` (or a
    :class:`HostLanes` of it) through per-lane host views."""
    lanes = ops if isinstance(ops, HostLanes) else HostLanes(ops)
    x = jax.device_get(res.x)
    allocs = np.stack([
        np.asarray(problem.extract(lanes.lane(i), x[i], pop_plan.idx[i]))
        for i in range(pop_plan.k)
    ])
    if pop_plan.replication is None:
        return coalesce_concat(allocs, pop_plan.idx, pop_plan.n_entities)
    return coalesce_replicated(allocs, pop_plan.idx, pop_plan.replication)


# --------------------------------------------------------------------------
# the one-call wrapper
# --------------------------------------------------------------------------

def _require_finite_ops(ops: OperatorLP, where: str) -> None:
    """Reject NaN/inf instance data before it reaches the solver.

    BIG-sentinel bounds are finite by construction (``core/problem.py``),
    so any genuine non-finite value in the built operator means the
    *instance* carried NaN/inf (bad rates, corrupted demands).  Raising
    here with the field name beats the alternative — a silently garbage
    allocation, or a divergence quarantine blamed on the warm start.
    Host-side scalar reads are fine at this boundary: it runs before the
    map-step backends (the steady-state host-sync tripwire arms around
    those only), and it is not reachable from ``solve_stacked``.
    """
    def _nonfinite(a) -> bool:
        a = jnp.asarray(a)
        if not jnp.issubdtype(a.dtype, jnp.floating):
            return False
        return not bool(jnp.all(jnp.isfinite(a)))

    for name in ("c", "q", "l", "u"):
        if _nonfinite(getattr(ops, name)):
            raise ValueError(
                f"non-finite instance data reached {where}: field {name!r} "
                "contains NaN/inf — fix the instance (rates/demands/bounds) "
                "before solving")
    for group_name, group in (("data", ops.data),
                              ("structured", ops.structured)):
        if group is None:
            continue
        leaves = jax.tree_util.tree_flatten_with_path(group)[0]
        for path, leaf in leaves:
            if _nonfinite(leaf):
                key = jax.tree_util.keystr(path)
                raise ValueError(
                    f"non-finite instance data reached {where}: operator "
                    f"field {group_name}{key} contains NaN/inf — fix the "
                    "instance (constraint matrices) before solving")


def _ids_or_positional(ids, n: int) -> np.ndarray:
    return np.arange(n) if ids is None else np.asarray(ids)


def _plan_fits(prev: PopPlan, problem: POPProblem, k: int,
               entity_ids: Optional[np.ndarray]) -> bool:
    """Can ``prev`` be reused verbatim for this instance?"""
    return (prev.k == k and prev.n_entities == problem.n_entities
            and np.array_equal(_ids_or_positional(entity_ids,
                                                  problem.n_entities),
                               prev.external_ids()))


def _plan_of(warm) -> Optional[PopPlan]:
    """The plan a warm result was computed under; reconstructed from the
    pre-plan (idx, replication) fields for results that predate PopPlan."""
    p = getattr(warm, "plan", None)
    if p is not None:
        return p
    idx = getattr(warm, "idx", None)
    if idx is None:
        return None
    rep = getattr(warm, "replication", None)
    ent = idx if rep is None else rep.entity_of(idx)
    n = int(ent.max()) + 1 if ent.size else 0
    return PopPlan(k=idx.shape[0], n_entities=n, idx=idx,
                   entity_of_slot=ent, replication=rep)


@dataclasses.dataclass
class PreparedSolve:
    """Stages plan+build of the pipeline, stopped at the map-step boundary.

    :func:`prepare_instance` produces one (plan resolved, sub-LPs built
    and stacked, warm start remapped, ``"auto"`` backend/engine resolved);
    the map-step launch itself — ``backends.get_backend(backend)(batch,
    ...)`` on ``backends.make_batch(ops, warm)`` — can then run anywhere
    (inline, or coalesced with other tenants' prepared solves by the
    serving dispatcher), and :func:`finish_prepared` turns the launch's
    :class:`SolveResult` back into a :class:`POPResult`.
    ``solve_instance`` is exactly ``prepare -> launch -> finish``."""

    problem: POPProblem
    plan: Optional[PopPlan]
    ops: OperatorLP
    warm: object                 # None | (x, y) | WarmStart
    warm_stats: Optional[dict]
    plan_source: str
    backend: str
    engine: object               # "matvec" | StepEngine
    opts: dict
    solver_kw: dict
    build_time_s: float


def prepare_instance(
    problem: POPProblem,
    solve_cfg: SolveConfig = SolveConfig(),
    exec_cfg: ExecConfig = ExecConfig(),
    *,
    warm: Optional[POPResult] = None,
    plan: Optional[PopPlan] = None,
    replan: bool = False,
    partition_idx: Optional[np.ndarray] = None,
    entity_ids: Optional[np.ndarray] = None,
    cold_lanes: Optional[np.ndarray] = None,
) -> PreparedSolve:
    """Everything :func:`solve_instance` does BEFORE the map-step launch:
    plan resolution (reuse / repair / fresh), sub-LP build + stack, warm
    start resolution (remap, quarantine masking), and ``"auto"``
    backend/engine resolution — returning a :class:`PreparedSolve` whose
    launch the caller owns.  See :func:`solve_instance` for the parameter
    semantics."""
    # honour the SolveConfig.min_per_sub promise HERE (the canonical
    # entry), not in each caller; without min_per_sub the requested k is
    # used verbatim (the historical pop_solve semantics)
    k = (solve_cfg.k if solve_cfg.min_per_sub is None
         else solve_cfg.k_for(problem.n_entities))
    solver_kw = exec_cfg.solver_dict()
    if warm is not None and getattr(warm, "x", None) is None:
        raise ValueError("warm result lacks solver state (x/y)")

    t0 = time.perf_counter()
    prev_plan = _plan_of(warm) if warm is not None else None
    # one side naming entities externally while the other matches by
    # position would pair arbitrary entities — refuse to match, start cold
    ids_agree = (prev_plan is None
                 or (prev_plan.entity_ids is None) == (entity_ids is None))
    source = "fresh"
    with obs.span("pop.plan") as plan_span:
        if plan is not None:
            p = plan
            source = "provided"
        elif (warm is not None and prev_plan is not None and not replan
              and partition_idx is None
              and solve_cfg.replicate_threshold is None and ids_agree):
            if _plan_fits(prev_plan, problem, k, entity_ids):
                p = prev_plan
                source = "reused"
            elif prev_plan.k == k and prev_plan.replication is None:
                # entity churn at the same k: repair the old plan in place
                # — survivors keep their (lane, slot), so the remapped warm
                # start lands in an unchanged lane context (see
                # plan.repair_plan)
                p = repair_plan(prev_plan, problem, entity_ids=entity_ids)
                source = "repaired"
            else:
                p = make_plan(problem, k, strategy=solve_cfg.strategy,
                              seed=solve_cfg.seed, entity_ids=entity_ids)
        else:
            p = make_plan(problem, k, strategy=solve_cfg.strategy,
                          seed=solve_cfg.seed,
                          replicate_threshold=solve_cfg.replicate_threshold,
                          partition_idx=partition_idx, entity_ids=entity_ids)
        plan_span.set(source=source)
    with obs.span("pop.build"):
        ops = build(problem, p)
        _require_finite_ops(ops, "solve_instance")
    build_time = time.perf_counter() - t0

    with obs.span("pop.remap"):
        warm_in = None
        warm_stats = None
        if warm is not None:
            if source == "reused":
                # identity churn: the PR-2 path, previous iterates verbatim
                warm_in = (warm.x, warm.y)
                n_live = int((p.entity_of_slot >= 0).sum())
                warm_stats = dict(warm_fraction=1.0, matched=n_live, fresh=0,
                                  dropped=0, lanes_cold=0, identity=True)
            elif not ids_agree:
                warm_stats = dict(
                    warm_fraction=0.0, matched=0, fresh=0, dropped=0,
                    lanes_cold=k, identity=False,
                    reason="entity id spaces differ (one side has "
                           "entity_ids, the other is positional)")
            elif prev_plan is not None:
                ws = remap_warm(prev_plan, p, warm, ops=ops)
                warm_in = ws
                warm_stats = ws.stats

        if cold_lanes is not None and warm_in is not None:
            # divergence quarantine: poisoned lanes restart cold, survivors
            # keep their iterates (a data-level mask — same jit cache key)
            cl = np.asarray(cold_lanes, bool).reshape(-1)
            if cl.shape[0] != p.k:
                raise ValueError(f"cold_lanes has {cl.shape[0]} entries for "
                                 f"k={p.k} lanes")
            if isinstance(warm_in, WarmStart):
                wx, wy = warm_in.x, warm_in.y
                mask = np.asarray(warm_in.mask, bool) & ~cl
                stats = dict(warm_in.stats or {})
            else:
                wx, wy = warm_in
                mask = ~cl
                stats = dict(warm_stats or {})
            stats["quarantined_lanes"] = int(cl.sum())
            stats["lanes_cold"] = int((~mask).sum())
            stats["warm_fraction"] = float(
                stats.get("warm_fraction", 1.0) * mask.mean()) if p.k else 0.0
            stats["identity"] = False
            warm_in = WarmStart(x=wx, y=wy, mask=mask, stats=stats)
            warm_stats = stats

    # resolve "auto" specs HERE so the result can report what actually ran
    backend_name, engine_run, opts = backends_mod.resolve_exec(
        ops, problem.K_mv, problem.KT_mv, exec_cfg.backend, exec_cfg.engine,
        exec_cfg.opts_dict())
    return PreparedSolve(
        problem=problem, plan=p, ops=ops, warm=warm_in,
        warm_stats=warm_stats, plan_source=source, backend=backend_name,
        engine=engine_run, opts=opts, solver_kw=solver_kw,
        build_time_s=build_time)


def finish_prepared(prep: PreparedSolve, res: SolveResult,
                    solve_time_s: float) -> POPResult:
    """Stage 4 for a :class:`PreparedSolve` whose launch already ran:
    reduce per-lane allocations and assemble the :class:`POPResult`.
    Every field the result keeps comes to the host in one
    ``jax.device_get``; from there on no device program runs."""
    p = prep.plan
    host = jax.device_get(dict(
        x=res.x, y=res.y, iterations=res.iterations,
        converged=res.converged, primal_obj=res.primal_obj,
        diverged=res.diverged))
    lanes = HostLanes(prep.ops)
    alloc = reduce(prep.problem, p, lanes, res._replace(**host))
    return POPResult(
        alloc=alloc, idx=p.idx,
        solve_time_s=solve_time_s, build_time_s=prep.build_time_s,
        iterations=host["iterations"],
        converged=host["converged"],
        similarity=p.similarity or {},
        sub_objectives=host["primal_obj"],
        replication=p.replication,
        x=host["x"], y=host["y"],
        plan=p, warm_stats=prep.warm_stats,
        backend=prep.backend, engine=pdhg.engine_name(prep.engine),
        kernels=pdhg.engine_kernels(prep.engine),
        plan_source=prep.plan_source,
        diverged=host["diverged"],
        op_reads=lanes.reads,
    )


def solve_instance(
    problem: POPProblem,
    solve_cfg: SolveConfig = SolveConfig(),
    exec_cfg: ExecConfig = ExecConfig(),
    *,
    warm: Optional[POPResult] = None,
    plan: Optional[PopPlan] = None,
    replan: bool = False,
    partition_idx: Optional[np.ndarray] = None,
    entity_ids: Optional[np.ndarray] = None,
    cold_lanes: Optional[np.ndarray] = None,
) -> POPResult:
    """Run POP on ``problem``: :func:`plan` -> :func:`build` ->
    :func:`solve` -> :func:`reduce` in one call, configured by the two
    frozen config dataclasses (``core/config.py``): :class:`SolveConfig`
    says how to split (k, strategy, replication), :class:`ExecConfig` how
    to execute (backend, engine, solver keywords).  This is the canonical
    pipeline entry — :class:`~repro.service.PopService` sessions call it
    per step, and the legacy :func:`pop_solve` kwarg surface forwards
    here.  (Internally it is :func:`prepare_instance` -> the map-step
    launch -> :func:`finish_prepared`; the serving dispatcher drives those
    stages separately to coalesce concurrent tenants into one launch.)

    ``warm`` re-solves an UPDATED instance from a previous
    :class:`POPResult`.  While the instance shape is unchanged the previous
    plan is reused and every lane continues from its previous (x, y)
    iterates; across entity arrivals/departures, k changes or forced
    re-planning (``replan=True`` / explicit ``plan=``) the old iterates
    are remapped onto the new plan (see module docstring).  ``entity_ids``
    names entities stably across instances for that matching;
    ``partition_idx`` overrides the strategy with an explicit split.

    The result reports the backend/engine that ACTUALLY ran (``"auto"``
    resolved) and where its plan came from (``plan_source``: "reused" /
    "repaired" / "fresh" / "provided") — the observability the service
    plan cache and the benchmarks aggregate.

    ``cold_lanes`` ([k] bool) forces those lanes to start cold even when a
    warm start is supplied — the divergence-quarantine retry path:
    ``PopSession.step`` re-solves with ``plan=prev.plan`` and
    ``cold_lanes=prev.diverged`` so only the poisoned lanes restart while
    healthy lanes keep their iterates."""
    prep = prepare_instance(
        problem, solve_cfg, exec_cfg, warm=warm, plan=plan, replan=replan,
        partition_idx=partition_idx, entity_ids=entity_ids,
        cold_lanes=cold_lanes)
    t1 = time.perf_counter()
    res = solve(problem, prep.plan, prep.ops, backend=prep.backend,
                engine=prep.engine, solver_kw=prep.solver_kw,
                backend_opts=prep.opts, warm=prep.warm)
    return finish_prepared(prep, res, time.perf_counter() - t1)


def pop_solve(
    problem: POPProblem,
    k: int,
    *,
    strategy: str = "random",
    backend: str = "auto",
    engine: str = "auto",
    seed: int = 0,
    replicate_threshold: Optional[float] = None,
    partition_idx: Optional[np.ndarray] = None,
    solver_kw: Optional[dict] = None,
    backend_opts: Optional[dict] = None,
    warm: Optional[POPResult] = None,
    plan: Optional[PopPlan] = None,
    replan: bool = False,
    entity_ids: Optional[np.ndarray] = None,
) -> POPResult:
    """DEPRECATED kwarg surface over :func:`solve_instance` — collapse the
    loose kwargs into a :class:`SolveConfig` + :class:`ExecConfig` (or use
    a :class:`~repro.service.PopService` session for online re-solves) and
    call :func:`solve_instance`; results are bit-identical.  Kept as a
    thin forwarder so existing callers keep working."""
    warnings.warn(
        "pop_solve(problem, k, ...) is deprecated: use "
        "pop.solve_instance(problem, SolveConfig(k=..., strategy=...), "
        "ExecConfig(...)) or a repro.service.PopService session — results "
        "are identical when the configs mirror these kwargs (NOTE: "
        "SolveConfig defaults strategy='stratified'; pop_solve's default "
        "was 'random')",
        DeprecationWarning, stacklevel=2)
    return solve_instance(
        problem,
        SolveConfig(k=k, strategy=strategy, seed=seed,
                    replicate_threshold=replicate_threshold),
        ExecConfig(backend=backend, engine=engine,
                   solver_kw=dict(solver_kw or {}),
                   backend_opts=dict(backend_opts or {})),
        warm=warm, plan=plan, replan=replan, partition_idx=partition_idx,
        entity_ids=entity_ids)


@dataclasses.dataclass
class FullResult:
    """Unpartitioned (k=1) solve outcome, with the same observability as
    :class:`POPResult` (resolved backend/engine)."""

    alloc: np.ndarray
    res: SolveResult
    solve_time_s: float
    build_time_s: float
    backend: Optional[str] = None
    engine: Optional[str] = None
    kernels: Optional[str] = None


def prepare_full(problem: POPProblem, *,
                 warm: Optional[SolveResult] = None,
                 exec_cfg: Optional[ExecConfig] = None) -> PreparedSolve:
    """The pre-launch half of :func:`solve_full_ex`: build the full LP as
    a k=1 stack, resolve ``"auto"`` backend/engine on it, and batch the
    warm iterates — returning a :class:`PreparedSolve` (``plan=None``,
    ``plan_source="full"``) whose single-lane launch the caller owns (the
    serving dispatcher coalesces compatible k=1 stacks from concurrent
    tenants into one multi-lane launch)."""
    exec_cfg = exec_cfg or ExecConfig()
    solver_kw = exec_cfg.solver_dict()
    t0 = time.perf_counter()
    with obs.span("pop.build"):
        op = problem.build_full()
        _require_finite_ops(op, "solve_full_ex")
    build_time = time.perf_counter() - t0
    opb = jax.tree.map(lambda a: jnp.asarray(a)[None], op)
    backend_name, engine_run, opts = backends_mod.resolve_exec(
        opb, problem.K_mv, problem.KT_mv, exec_cfg.backend, exec_cfg.engine,
        exec_cfg.opts_dict())
    if warm is not None:
        if hasattr(warm, "x") and hasattr(warm, "y"):
            warm = (warm.x, warm.y)
        warm = tuple(jnp.asarray(w)[None] for w in warm)
    return PreparedSolve(
        problem=problem, plan=None, ops=opb, warm=warm, warm_stats=None,
        plan_source="full", backend=backend_name, engine=engine_run,
        opts=opts, solver_kw=solver_kw, build_time_s=build_time)


def finish_full(prep: PreparedSolve, res: SolveResult,
                solve_time_s: float) -> FullResult:
    """Unbatch a :func:`prepare_full` launch's k=1 result and extract the
    allocation — the post-launch half of :func:`solve_full_ex`."""
    res1 = jax.tree.map(lambda a: a[0], res)
    op = jax.tree.map(lambda a: a[0], prep.ops)
    idx = np.arange(prep.problem.n_entities)
    alloc = np.asarray(prep.problem.extract(op, np.asarray(res1.x), idx))
    return FullResult(alloc=alloc, res=res1, solve_time_s=solve_time_s,
                      build_time_s=prep.build_time_s, backend=prep.backend,
                      engine=pdhg.engine_name(prep.engine),
                      kernels=pdhg.engine_kernels(prep.engine))


def solve_full_ex(problem: POPProblem, *,
                  warm: Optional[SolveResult] = None,
                  exec_cfg: Optional[ExecConfig] = None) -> FullResult:
    """Unpartitioned baseline (the paper's 'original problem') as a k=1
    stack through the SAME execution substrate as the POP path — so
    full-problem baselines get the fused step engine, explicit backend
    selection and the jit-cached map solver too.  Everything about the
    execution (including ``solver_kw``) comes from ``exec_cfg``; ``warm``
    re-solves from a previous full-problem :class:`SolveResult`.  Returns
    a :class:`FullResult` reporting the resolved backend/engine."""
    prep = prepare_full(problem, warm=warm, exec_cfg=exec_cfg)
    t1 = time.perf_counter()
    res = backends_mod.solve_map(
        prep.ops, problem.K_mv, problem.KT_mv, prep.solver_kw,
        backend=prep.backend, engine=prep.engine, warm=prep.warm,
        **prep.opts)
    jax.block_until_ready(res.x)
    return finish_full(prep, res, time.perf_counter() - t1)


def solve_full(problem: POPProblem, solver_kw: Optional[dict] = None,
               warm: Optional[SolveResult] = None, *,
               backend: str = "auto", engine: str = "auto",
               backend_opts: Optional[dict] = None):
    """Tuple-returning wrapper over :func:`solve_full_ex` (the historical
    surface: ``(alloc, res, solve_time, build_time)``)."""
    r = solve_full_ex(
        problem, warm=warm,
        exec_cfg=ExecConfig(backend=backend, engine=engine,
                            solver_kw=dict(solver_kw or {}),
                            backend_opts=dict(backend_opts or {})))
    return r.alloc, r.res, r.solve_time_s, r.build_time_s
