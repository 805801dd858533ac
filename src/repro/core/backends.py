"""POP map-step execution substrate: pluggable backends for the batched solve.

POP's whole speedup lives in the map step — k independent sub-LPs solved
with ZERO collectives (they share no variables by construction).  How those
k solves are *executed* is an orthogonal choice, so it lives here as a
registry of interchangeable backends, all with the same contract:

    backend(batch, K_mv, KT_mv, solver_kw, engine=..., **opts) -> SolveResult

where ``batch = (ops, warm_x, warm_y)``: an :class:`~repro.core.pdhg.
OperatorLP` pytree stacked on a leading axis of length k plus the starting
iterates for every lane (cold starts are materialised up front by
:func:`solve_map`, so warm-started online re-solves flow through exactly
the same code path as cold ones).  The result carries the same leading
axis.  Backends differ only in scheduling, never in math — every backend
must match ``vmap`` to float tolerance (``tests/test_backends.py``).

Four *step engines* (see ``core/pdhg.py``) plug into every backend:
``engine="matvec"`` vmaps the per-problem operator matvecs (any structured
LP), ``engine="fused"`` hands the whole stacked batch to the fused Pallas
matmul kernels in one launch per half-step (dense LPs; compiled on TPU,
XLA-fused reference elsewhere), ``engine="fused_structured"`` does the
same through batched gather/segment-reduce kernels for operators carrying
:class:`~repro.core.pdhg.StructuredOperator` index metadata (the
segment-sum matvecs of the structured paper domains), and
``engine="fused_structured_full"`` is the M-blocked streaming variant for
the single-lane unpartitioned problem (the ``solve_full`` baseline at
paper scale).  ``engine="auto"`` picks per
:func:`repro.core.pdhg.select_engine` — structured-fused whenever index
metadata is present, the streaming full engine when additionally
single-lane with large wide buckets.  :func:`resolve_exec` resolves specs
*outside* jit with concrete operators, which is what lets the full
engine's static ragged wide-block plan be computed from values.

Registered backends:

``serial``
    Python loop over the k sub-problems, one jitted k=1 solve each.  The
    reference/debugging backend: what the other four must reproduce.
``vmap``
    One batched solve on one device.  Best below the device-memory knee.
``chunked_vmap``
    ``lax.map`` over fixed-size batched chunks: peak memory is bounded by
    the chunk size, not k, so huge k fits on one device at the cost of a
    sequential walk over chunks.
``shard_map``
    Sub-problems spread over a mesh axis, solved batched within each
    shard.  k is padded up to a multiple of the device count with dummy
    sub-problems (replicas of sub-problem 0) and the padding is sliced off
    afterwards — no device idles, and results are bit-identical to the
    unpadded solve (each lane is independent, so extra lanes cannot
    perturb real ones).
``pmap``
    Same layout via ``jax.pmap`` — the fallback for JAX versions or
    platforms where shard_map misbehaves.

``backend="auto"`` picks by device count, k, and per-sub-problem size
(:func:`select_backend`).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import compat
from . import pdhg
from .pdhg import OperatorLP, SolveResult, StepEngine

MapBackend = Callable[..., SolveResult]

MAP_BACKENDS: Dict[str, MapBackend] = {}

# chunked_vmap default chunk; auto-selection switches off plain vmap above
# this many sub-problems (CPU-sized default — meshes usually decide first)
DEFAULT_CHUNK = 16
AUTO_VMAP_MAX_K = 64
# ... or above this many floats of stacked problem data (~256 MB fp32)
AUTO_VMAP_MAX_ELEMS = 64_000_000

# per-platform MEASURED overrides of the auto-selection constants above,
# installed from a TuningProfile (``PopService(profile=...)`` /
# install_tuned_thresholds); empty = the hand-set constants decide.
# Process-wide by design — like the jit caches these thresholds describe
# the hardware, not one service instance.
_TUNED_THRESHOLDS: Dict[str, dict] = {}


def install_tuned_thresholds(per_platform: Optional[dict]) -> None:
    """Install measured ``backend="auto"`` thresholds keyed by JAX
    platform name (``{"cpu": {"vmap_max_k": ..., "vmap_max_elems": ...}}``
    — the ``backend_thresholds`` table of a validated
    :class:`repro.tuning.TuningProfile`).  ``None``/empty clears back to
    the constants."""
    _TUNED_THRESHOLDS.clear()
    for platform, t in (per_platform or {}).items():
        if isinstance(t, dict):
            _TUNED_THRESHOLDS[str(platform)] = dict(t)


def _auto_thresholds() -> Tuple[int, int]:
    """(vmap_max_k, vmap_max_elems) for the current platform: the
    installed measured values when a profile provided them, else the
    constants."""
    t = _TUNED_THRESHOLDS.get(jax.default_backend())
    if not t:
        return AUTO_VMAP_MAX_K, AUTO_VMAP_MAX_ELEMS
    return (int(t.get("vmap_max_k", AUTO_VMAP_MAX_K)),
            int(t.get("vmap_max_elems", AUTO_VMAP_MAX_ELEMS)))


EngineSpec = Union[str, StepEngine]


def register_backend(name: str) -> Callable[[MapBackend], MapBackend]:
    def deco(fn: MapBackend) -> MapBackend:
        MAP_BACKENDS[name] = fn
        return fn
    return deco


def available_backends() -> tuple:
    return tuple(MAP_BACKENDS)


def get_backend(name: str) -> MapBackend:
    if name not in MAP_BACKENDS:
        raise ValueError(
            f"unknown map backend {name!r}; registered: {sorted(MAP_BACKENDS)}")
    return MAP_BACKENDS[name]


# --------------------------------------------------------------------------
# padding: k -> multiple of the device axis
# --------------------------------------------------------------------------

def batch_size(tree) -> int:
    """Leading-axis length of any stacked pytree (ops or (ops, wx, wy))."""
    return jax.tree.leaves(tree)[0].shape[0]


def pad_to_multiple(tree, m: int):
    """Pad the stacked sub-problem axis of any pytree to a multiple of ``m``
    by repeating lane 0.  Returns ``(padded, k)`` with the ORIGINAL k, so
    the caller slices ``[:k]`` off every result leaf.  Dummy lanes solve a
    real (already-solved-elsewhere) LP and are discarded; lanes are
    independent, so the real lanes' trajectories are unchanged."""
    k = batch_size(tree)
    pad = (-k) % m
    if pad == 0:
        return tree, k
    padded = jax.tree.map(
        lambda a: jnp.concatenate(
            [a, jnp.broadcast_to(a[:1], (pad,) + a.shape[1:])]),
        tree)
    return padded, k


def _slice_result(res: SolveResult, k: int) -> SolveResult:
    return jax.tree.map(lambda a: a[:k], res)


# --------------------------------------------------------------------------
# the per-batch solver (shared by every backend)
# --------------------------------------------------------------------------

def cold_start(ops: OperatorLP) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The solver's default starting iterates, materialised eagerly so warm
    and cold solves share one code path (bit-identical to passing no warm
    start: x0 = clip(0, l, u), y0 = 0)."""
    return (jnp.clip(jnp.zeros_like(ops.c), ops.l, ops.u),
            jnp.zeros_like(ops.q))


def _freeze_kw(solver_kw: dict):
    try:
        return tuple(sorted(solver_kw.items())), True
    except TypeError:
        return tuple(solver_kw.items()), False


@functools.lru_cache(maxsize=64)
def _cached_solver(K_mv, KT_mv, kw_items, engine):
    return jax.jit(_build_solver(K_mv, KT_mv, dict(kw_items), engine))


def _build_solver(K_mv, KT_mv, solver_kw: dict, engine: EngineSpec):
    """The map solver ``fn(batch)``.  Its name (``map_solve_matvec`` /
    ``map_solve_stacked``) names the XLA module in a profiler trace."""
    if engine == "matvec":
        # vmap over per-lane k=1 solves (pdhg.solve IS solve_stacked at
        # k=1 — same loop) rather than one native k-stack: per-lane XLA
        # numerics are then independent of the batch size, which is what
        # lets serial/chunked/shard_map/pmap match vmap bit-for-bit.
        sol = functools.partial(pdhg.solve, K_mv=K_mv, KT_mv=KT_mv, **solver_kw)

        def map_solve_matvec(batch):
            return jax.vmap(
                lambda o, wx, wy: sol(o, warm_x=wx, warm_y=wy))(*batch)
        return map_solve_matvec
    if not isinstance(engine, StepEngine):
        raise ValueError(f"unresolved engine {engine!r} reached a backend; "
                         "go through solve_map or pass a StepEngine")

    def map_solve_stacked(batch):
        return pdhg.solve_stacked(batch[0], engine=engine, warm_x=batch[1],
                                  warm_y=batch[2], **solver_kw)
    return map_solve_stacked


def make_map_solver(K_mv, KT_mv, solver_kw: Optional[dict] = None,
                    engine: EngineSpec = "matvec"):
    """Jitted ``fn(batch) -> SolveResult`` for one stacked batch, where
    ``batch = (ops, warm_x, warm_y)``.  The jitted function is cached on
    (matvecs, solver_kw, engine) when hashable, so online re-solves reuse
    the compilation instead of retracing every round (engine objects from
    :func:`pdhg.fused_dense_engine` are themselves cached, so the default
    fused engine hits this cache too).  Nesting the returned function
    inside lax.map/shard_map/pmap just inlines its jaxpr."""
    solver_kw = dict(solver_kw or {})
    kw_items, hashable = _freeze_kw(solver_kw)
    if hashable:
        try:
            return _cached_solver(K_mv, KT_mv, kw_items, engine)
        except TypeError:
            pass
    # unhashable solver_kw / matvecs: a fresh jit per call is the documented
    # degradation (callers wanting cache hits pass hashable configs)
    return jax.jit(_build_solver(K_mv, KT_mv, solver_kw, engine))


# --------------------------------------------------------------------------
# memoized outer runners: the jit/pmap wrapper around a map solver must be
# built ONCE per (inner solver, layout) — jax.jit keys its own cache on the
# wrapped callable's identity, so re-wrapping per call recompiles the whole
# solver every invocation (the retrace popcheck's `retrace-hazard` rule and
# tests/test_retrace_guard.py pin this)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=64)
def _chunked_runner(inner):
    """jit(lax.map(inner)) over [n_chunks, chunk, ...] stacked chunks."""
    def chunked_map_solve(chunks):
        return jax.lax.map(inner, chunks)
    return jax.jit(chunked_map_solve)


@functools.lru_cache(maxsize=64)
def _result_treedef(inner, in_treedef, shapes_dtypes):
    """Output tree structure of ``inner`` for a given input layout —
    abstract eval only, memoized so steady-state re-solves skip even the
    trace."""
    leaves = [jax.ShapeDtypeStruct(s, d) for s, d in shapes_dtypes]
    batch = jax.tree.unflatten(in_treedef, leaves)
    return jax.tree.structure(jax.eval_shape(inner, batch))


def _tree_key(tree):
    """Hashable (treedef, shapes/dtypes) layout key for a stacked batch."""
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, tuple((l.shape, jnp.asarray(l).dtype.name)
                          for l in leaves)


@functools.lru_cache(maxsize=64)
def _shard_runner(inner, mesh, axis, chunk, in_treedef, shapes_dtypes):
    """jit(shard_map(...)) for one (solver, mesh, chunking, layout)."""
    if chunk:
        def local_solve(local_batch):
            chunked = jax.tree.map(
                lambda a: a.reshape((a.shape[0] // chunk, chunk)
                                    + a.shape[1:]), local_batch)
            res = jax.lax.map(inner, chunked)
            return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                                res)
    else:
        local_solve = inner
    spec = jax.tree.unflatten(in_treedef,
                              [P(axis)] * in_treedef.num_leaves)
    out_treedef = _result_treedef(inner, in_treedef, shapes_dtypes)
    out_spec = jax.tree.unflatten(out_treedef,
                                  [P(axis)] * out_treedef.num_leaves)
    fn = compat.shard_map(local_solve, mesh=mesh, in_specs=(spec,),
                          out_specs=out_spec,
                          # solver constants (power-iteration seed vectors)
                          # are unvarying while problem data varies over the
                          # POP axis — exactly the intent; skip the check
                          check=False)
    return jax.jit(fn)


@functools.lru_cache(maxsize=64)
def _pmap_runner(inner, devices: tuple):
    return jax.pmap(inner, devices=list(devices))


# --------------------------------------------------------------------------
# backends
# --------------------------------------------------------------------------

@register_backend("serial")
def solve_serial(batch, K_mv, KT_mv, solver_kw,
                 engine: EngineSpec = "matvec") -> SolveResult:
    """One jitted k=1 solve per sub-problem, in a Python loop.  Slowest and
    simplest — the numerical reference the parallel backends must match."""
    fn = make_map_solver(K_mv, KT_mv, solver_kw, engine)
    outs = [fn(jax.tree.map(lambda a: a[i:i + 1], batch))
            for i in range(batch_size(batch))]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *outs)


@register_backend("vmap")
def solve_vmap(batch, K_mv, KT_mv, solver_kw,
               engine: EngineSpec = "matvec") -> SolveResult:
    return make_map_solver(K_mv, KT_mv, solver_kw, engine)(batch)


@register_backend("chunked_vmap")
def solve_chunked_vmap(batch, K_mv, KT_mv, solver_kw,
                       engine: EngineSpec = "matvec",
                       chunk: int = DEFAULT_CHUNK) -> SolveResult:
    """``lax.map`` over batched chunks: peak memory ~ one chunk of
    sub-problems instead of all k.  k pads up to a chunk multiple."""
    k = batch_size(batch)
    chunk = max(1, min(chunk, k))
    padded, _ = pad_to_multiple(batch, chunk)
    k_pad = batch_size(padded)
    chunked = jax.tree.map(
        lambda a: a.reshape((k_pad // chunk, chunk) + a.shape[1:]), padded)
    inner = make_map_solver(K_mv, KT_mv, solver_kw, engine)
    res = _chunked_runner(inner)(chunked)
    res = jax.tree.map(lambda a: a.reshape((k_pad,) + a.shape[2:]), res)
    return _slice_result(res, k)


@register_backend("shard_map")
def solve_shard_map(batch, K_mv, KT_mv, solver_kw,
                    engine: EngineSpec = "matvec",
                    mesh: Optional[Mesh] = None,
                    axis: str = "pop",
                    chunk: Optional[int] = None) -> SolveResult:
    """Shard the k sub-problems over a mesh axis; solve batched within each
    shard.  No collectives in the mapped body — POP sub-problems are
    independent by construction.  Goes through :mod:`repro.core.compat` so
    it runs on any JAX that has shard_map under either name/kwarg spelling.

    ``chunk`` bounds per-device memory the same way chunked_vmap does on
    one device: each shard walks its lanes in batched chunks of that size
    (``None`` = decide from the per-device share: chunk only when it
    exceeds the single-device vmap ceiling; ``0`` = never chunk)."""
    if mesh is None:
        mesh = Mesh(np.array(jax.devices()), (axis,))
    n_dev = mesh.shape[axis]
    if chunk is None:
        per_dev = -(-batch_size(batch) // n_dev)
        max_k, max_elems = _auto_thresholds()
        heavy = (per_dev > max_k
                 or per_dev * max(_n_elems_per_sub(batch[0]), 1)
                 > max_elems)
        chunk = DEFAULT_CHUNK if heavy else 0
    padded, k = pad_to_multiple(batch, n_dev * chunk if chunk else n_dev)

    inner = make_map_solver(K_mv, KT_mv, solver_kw, engine)
    in_treedef, shapes_dtypes = _tree_key(padded)
    fn = _shard_runner(inner, mesh, axis, chunk, in_treedef, shapes_dtypes)
    return _slice_result(fn(padded), k)


@register_backend("pmap")
def solve_pmap(batch, K_mv, KT_mv, solver_kw,
               engine: EngineSpec = "matvec",
               devices: Optional[list] = None) -> SolveResult:
    """Per-device batched shards via ``jax.pmap`` — fallback when shard_map
    is unusable on the installed JAX/platform."""
    devices = list(devices if devices is not None else jax.devices())
    n_dev = len(devices)
    padded, k = pad_to_multiple(batch, n_dev)
    k_pad = batch_size(padded)
    sharded = jax.tree.map(
        lambda a: a.reshape((n_dev, k_pad // n_dev) + a.shape[1:]), padded)
    fn = _pmap_runner(make_map_solver(K_mv, KT_mv, solver_kw, engine),
                      tuple(devices))
    res = fn(sharded)
    res = jax.tree.map(lambda a: a.reshape((k_pad,) + a.shape[2:]), res)
    return _slice_result(res, k)


# --------------------------------------------------------------------------
# auto-selection + entry point
# --------------------------------------------------------------------------

def select_backend(k: int, n_elems_per_sub: int = 0,
                   n_dev: Optional[int] = None) -> str:
    """Pick a backend from (k, per-sub-problem element count, devices).

    Multi-device and enough sub-problems to fill the mesh -> ``shard_map``
    (each device solves its own lanes, zero communication).  Single device
    -> ``vmap`` until the stacked batch gets big (many lanes or a large
    stacked footprint), then ``chunked_vmap`` to bound peak memory.
    The crossover thresholds are the hand-set constants unless a
    :class:`repro.tuning.TuningProfile` installed measured per-platform
    values (:func:`install_tuned_thresholds`).
    """
    n_dev = compat.device_count() if n_dev is None else n_dev
    if n_dev > 1 and k >= n_dev:
        # memory-safe at any k: solve_shard_map self-chunks each shard when
        # the per-device share exceeds the single-device vmap ceiling
        return "shard_map"
    max_k, max_elems = _auto_thresholds()
    if k > max_k or k * max(n_elems_per_sub, 1) > max_elems:
        return "chunked_vmap"
    return "vmap"


def _n_elems_per_sub(ops: OperatorLP) -> int:
    return sum(int(np.prod(l.shape[1:])) for l in jax.tree.leaves(ops))


def _resolve_warm(ops: OperatorLP, warm) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Starting iterates from ``warm``: None (cold), a SolveResult-like
    object with .x/.y, an (x, y) pair, or a masked
    :class:`~repro.core.plan.WarmStart` — each stacked [k, ...].

    A WarmStart's per-lane ``mask`` is applied HERE, as data: masked-out
    lanes get the cold iterates via ``jnp.where``, so remapped warm starts
    with cold lanes flow through the same jitted solve as everything else
    (no Python-level branch, no retrace)."""
    if warm is None:
        return cold_start(ops)
    mask = getattr(warm, "mask", None)
    if hasattr(warm, "x") and hasattr(warm, "y"):
        wx, wy = warm.x, warm.y
    else:
        wx, wy = warm
    wx = jnp.asarray(wx, ops.c.dtype)
    wy = jnp.asarray(wy, ops.q.dtype)
    if wx.shape != ops.c.shape or wy.shape != ops.q.shape:
        raise ValueError(
            f"warm-start shapes {wx.shape}/{wy.shape} do not match the "
            f"stacked problem {ops.c.shape}/{ops.q.shape} — for warm "
            "re-solves across partition changes go through pop_solve(warm=) "
            "or core.plan.remap_warm, which rebuild matching iterates")
    if mask is not None:
        m = jnp.asarray(mask, bool)[:, None]
        cx, cy = cold_start(ops)
        wx = jnp.where(m, wx, cx)
        wy = jnp.where(m, wy, cy)
    return wx, wy


def make_batch(ops: OperatorLP, warm=None):
    """The ``(ops, warm_x, warm_y)`` batch a map backend consumes, with
    cold lanes materialised (see :func:`_resolve_warm`).  This is the
    exact batch :func:`solve_map` builds — exposed so the serving
    dispatcher can assemble per-tenant batches on the caller thread and
    hand the map-step launch to a shared worker."""
    return (ops, *_resolve_warm(ops, warm))


# --------------------------------------------------------------------------
# cross-tenant coalescing: shared launches over concatenated batches
# --------------------------------------------------------------------------

def coalesce_key(ops: OperatorLP, K_mv, KT_mv, backend: str,
                 engine: EngineSpec, solver_kw: dict, opts: dict):
    """Hashable compatibility key for sharing one map-step launch across
    prepared batches — the jit-cache key contract under shared launches:
    two batches with EQUAL keys run the same compiled solver
    (``_cached_solver`` keys on the same matvecs / solver_kw / engine) and
    may be lane-concatenated into one call without changing any lane's
    trajectory.

    Per-lane array layouts must match exactly EXCEPT structured ELL
    widths and wide-bucket counts, which :func:`~repro.core.pdhg.
    concat_stacks` pads to the group maximum (so the key records only
    their ndim/dtype).  Returns ``None`` — never coalesce — for the
    single-lane streaming engine (``fused_structured_full`` rejects
    multi-lane stacks by design) and for unhashable configs/matvecs
    (which would retrace per call anyway)."""
    kw_items, hashable = _freeze_kw(solver_kw)
    if not hashable:
        return None
    try:
        opt_items = tuple(sorted(opts.items()))
        hash((opt_items, K_mv, KT_mv, engine))
    except TypeError:
        return None
    if isinstance(engine, StepEngine) and engine.name == "fused_structured_full":
        return None
    bare = ops._replace(structured=None)
    leaves, treedef = jax.tree.flatten(bare)
    lane_shapes = tuple((l.shape[1:], jnp.asarray(l).dtype.name)
                        for l in leaves)
    s = ops.structured
    skey = None if s is None else tuple(
        None if v is None else (v.ndim, jnp.asarray(v).dtype.name)
        for v in s)
    return (treedef, lane_shapes, skey, K_mv, KT_mv, backend, engine,
            kw_items, opt_items)


def concat_batches(batches):
    """Concatenate per-tenant ``(ops, warm_x, warm_y)`` batches on the lane
    axis into one launch-sized batch (ops via
    :func:`~repro.core.pdhg.concat_stacks`, which pads structured ELL
    widths across tenants).  Returns ``(batch, sizes)`` — undo with
    :func:`split_result`."""
    sizes = tuple(batch_size(b) for b in batches)
    ops = pdhg.concat_stacks([b[0] for b in batches])
    wx = jnp.concatenate([b[1] for b in batches])
    wy = jnp.concatenate([b[2] for b in batches])
    return (ops, wx, wy), sizes


def split_result(res: SolveResult, sizes) -> list:
    """Slice a concatenated launch's SolveResult back into per-tenant
    results (lane ranges in submission order)."""
    outs, start = [], 0
    for s in sizes:
        outs.append(jax.tree.map(
            lambda a, i0=start, i1=start + s: a[i0:i1], res))
        start += s
    return outs


def next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pad_lanes_pow2(batch):
    """Pad a coalesced batch's lane count up to the next power of two by
    repeating lane 0 (see :func:`pad_to_multiple` — dummy lanes cannot
    perturb real ones), so variable group sizes compile O(log) distinct
    lane counts instead of one per arrival pattern.  Returns
    ``(padded, k)`` with the original k; slice results ``[:k]``."""
    return pad_to_multiple(batch, next_pow2(batch_size(batch)))


def solve_one_ex(op: OperatorLP, K_mv, KT_mv,
                 solver_kw: Optional[dict] = None,
                 backend: str = "auto", engine: EngineSpec = "auto",
                 warm=None, **opts: Any):
    """Solve ONE unbatched LP through the same substrate as the map step
    (a k=1 stack) and report what ran: returns
    ``(result, backend_name, engine_name)`` with ``"auto"`` resolved.
    The operator is batched exactly ONCE (the same stack serves the
    resolution probe and the solve); ``warm`` is an unbatched (x, y) pair
    or SolveResult-like object; the result is unbatched again."""
    opb = jax.tree.map(lambda a: jnp.asarray(a)[None], op)
    backend, engine, opts = resolve_exec(opb, K_mv, KT_mv, backend, engine,
                                         opts)
    if warm is not None:
        if hasattr(warm, "x") and hasattr(warm, "y"):
            warm = (warm.x, warm.y)
        warm = tuple(jnp.asarray(w)[None] for w in warm)
    res = solve_map(opb, K_mv, KT_mv, solver_kw, backend=backend,
                    engine=engine, warm=warm, **opts)
    jax.block_until_ready(res.x)
    return (jax.tree.map(lambda a: a[0], res), backend,
            pdhg.engine_name(engine))


def solve_one(op: OperatorLP, K_mv, KT_mv, solver_kw: Optional[dict] = None,
              backend: str = "auto", engine: EngineSpec = "auto",
              warm=None, **opts: Any) -> SolveResult:
    """:func:`solve_one_ex` without the observability tuple — full-problem
    baselines get the engine selection, the backend registry and the
    jit-cached map solver without hand-rolling the batch/unbatch dance."""
    res, _, _ = solve_one_ex(op, K_mv, KT_mv, solver_kw, backend=backend,
                             engine=engine, warm=warm, **opts)
    return res


def resolve_exec(ops: OperatorLP, K_mv, KT_mv, backend: str = "auto",
                 engine: EngineSpec = "auto",
                 opts: Optional[dict] = None):
    """Resolve ``"auto"`` specs to the (backend name, engine) that will
    actually run — the single resolution point :func:`solve_map` uses, and
    the observability hook the pipeline records into ``POPResult.backend``
    / ``.engine`` (callers and benchmarks otherwise can't see what
    ``"auto"`` picked).  Returns ``(backend_name, engine, opts)`` where
    ``engine`` is ``"matvec"`` or a resolved
    :class:`~repro.core.pdhg.StepEngine` (``pdhg.engine_name`` prints it);
    under ``backend="auto"``, ``opts`` the winning backend doesn't take
    (e.g. ``chunk=`` when vmap wins) are dropped — they are hints for
    *whichever* backend wins, not requirements.  An explicitly named
    backend keeps opts verbatim (and still rejects unknown ones when
    called)."""
    if engine == "auto" or engine is None:
        engine = pdhg.select_engine(ops, K_mv, KT_mv)
    if engine != "matvec":
        # canonical resolution/validation lives in pdhg.resolve_engine;
        # "matvec" stays a string so _build_solver takes the vmapped path
        engine = pdhg.resolve_engine(engine, ops, K_mv, KT_mv)
    opts = dict(opts or {})
    if backend == "auto":
        backend = select_backend(batch_size(ops), _n_elems_per_sub(ops))
        if opts:
            import inspect
            accepted = inspect.signature(get_backend(backend)).parameters
            opts = {k: v for k, v in opts.items() if k in accepted}
    else:
        get_backend(backend)          # fail fast on unknown names
    return backend, engine, opts


def solve_map(ops: OperatorLP, K_mv, KT_mv, solver_kw: Optional[dict] = None,
              backend: str = "auto", engine: EngineSpec = "auto",
              warm=None, **opts: Any) -> SolveResult:
    """Run the POP map step on stacked ``ops`` with the named backend
    (``"auto"`` resolves via :func:`select_backend`) and step engine
    (``"auto"`` resolves via :func:`repro.core.pdhg.select_engine`) —
    both through :func:`resolve_exec`, so callers who need to report what
    actually ran can resolve first and pass the resolved values in (the
    second resolution is a no-op).

    ``warm`` seeds every lane from a previous solve of a nearby instance
    (a SolveResult, or an (x, y) pair) — the online re-solve path."""
    solver_kw = dict(solver_kw or {})
    backend, engine, opts = resolve_exec(ops, K_mv, KT_mv, backend, engine,
                                         opts)
    batch = make_batch(ops, warm)
    return get_backend(backend)(batch, K_mv, KT_mv, solver_kw,
                                engine=engine, **opts)
