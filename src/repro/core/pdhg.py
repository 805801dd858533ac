"""Restarted PDHG (PDLP-family) linear-programming solver in pure JAX.

Why PDHG and not simplex/barrier (what the paper's solvers use): every PDHG
iteration is two matrix–vector products plus element-wise projections —
MXU/VPU work with no data-dependent control flow.  That makes the solver

  * ``jax.lax``-expressible (while_loop/fori_loop),
  * **vmap-able over the POP sub-problem axis** — POP's map step becomes a
    single batched solve, and
  * ``shard_map``-able — sub-problems spread across mesh devices with zero
    collectives inside the map step (they are independent by construction).

The solver is generic over an *operator form* of the constraint matrix

    K = [G; A]   (first ``n_ineq`` rows are inequalities)

supplied as a pair of callables ``K_mv(data, x)`` / ``KT_mv(data, y)`` plus a
data pytree.  Dense problems use plain matmuls (and, on TPU, the Pallas
kernels in ``repro.kernels``); the big domain problems (traffic engineering
with >10^6 variables) supply structured matvecs so the full unpartitioned
baseline never materialises a dense K.  Structured problems can ALSO attach
a :class:`StructuredOperator` — explicit index arrays + coefficients — which
unlocks the ``fused_structured`` engine (below).

Step-engine contract
--------------------

The inner-loop math is factored behind a :class:`StepEngine`.  An engine
works on a whole STACKED batch of k sub-problems at once — every array
carries a leading ``[k]`` axis and per-sub-problem scalars (step sizes) are
``[k]`` vectors, because POP sub-problems restart independently and their
step sizes diverge across the batch.  An engine provides two *half-steps*
that each emit the matvec product they materialise:

    forward(data, x, c, l, u, tau[k], kty)          -> (x_new, K x_new)
    backward(data, y, q, sigma[k], ineq, kx, kx_-)  -> (y_new, K^T y_new)

``forward`` is the primal update ``x+ = clip(x - tau (c + K^T y), l, u)``
(consuming the CARRIED ``K^T y`` from the previous backward) followed by the
forward product ``K x+``; ``backward`` is the dual update using the
extrapolated product ``K x_bar = 2 K x+ - K x`` (linearity of K — no second
matvec for the extrapolated point) followed by the adjoint product
``K^T y+``.  Per iteration that is exactly one K and one K^T application —
the same operator work as classic PDHG — but the products now flow OUT of
the half-steps, which is what makes the in-loop KKT check free (below).
Four engines ship:

``matvec`` (:func:`matvec_engine`)
    Wraps the user's ``K_mv``/``KT_mv`` callables with ``jax.vmap`` and
    applies the element-wise tails in plain jnp.  Works for ANY structured
    operator; the fallback engine for problems without metadata.

``fused`` (:func:`fused_dense_engine`)
    Dense-data-only.  Routes each half-step through the batched fused
    kernels in ``repro.kernels.ops`` (``fused_forward_step`` /
    ``fused_backward_step``), so on TPU the matvec partials stay in VMEM
    and the axpy+projection tail runs in the SAME kernel launch — one
    launch per half-step for the whole k-stack.

``fused_structured`` (:func:`fused_structured_engine`)
    For operators with a :class:`StructuredOperator` attached (segment-sum
    /gather matvecs: Gavel per-job rows, traffic per-commodity path sums,
    LB server groups).  Each half-step is one batched Pallas
    gather/segment-reduce launch over the whole k-stack
    (``kernels/structured_pdhg_step.py``); off-TPU the dispatch in
    ``kernels/ops.py`` takes an XLA reference built on
    ``take_along_axis`` gathers — no scatters anywhere, unlike the
    ``segment_sum`` scatter-adds inside typical domain matvecs.

``fused_structured_full`` (:func:`fused_structured_full_engine`)
    The M-blocked streaming variant for the **single-lane full problem**
    (the k=1 quality baseline POP is judged against).  Tiles the nnz-major
    ELL arrays into VMEM-sized M-blocks, streams partial gather/reduces,
    folds wide-bucket contributions across blocks through the fold map
    (a gather, not a one-hot einsum), and slices the descending-sorted
    wide bucket by a static ragged block plan so padded work stays ~nnz.
    Supports int8/bf16 coefficient storage (:func:`quantize_structured`)
    with in-kernel dequantization and f32 accumulation.

``engine="auto"`` (:func:`select_engine`) picks ``fused`` for dense
operator data on TPU, ``fused_structured`` when index metadata is present
(``fused_structured_full`` when additionally single-lane with large wide
buckets), and ``matvec`` otherwise.  Engines differ only in
scheduling/fusion, never in math — ``tests/test_engine_conformance.py``
pins all engines x all map backends x the three paper domains to 1e-5 on
fixed iteration budgets.

In-loop KKT (free convergence checks)
-------------------------------------

Because the half-steps emit ``K x`` / ``K^T y``, and the running averages
of those products equal the products of the running averages (K is
linear), the per-chunk KKT check — primal residual and duality gap for
BOTH restart candidates (current iterate and running average) — is
computed entirely from carried products: **zero extra operator passes**.
The previous scheme paid two full K + two K^T applications per check.
``solve_stacked(kkt="standalone")`` keeps a verification mode that
re-derives the current candidate's products with fresh operator passes;
it must be bit-identical to the in-loop path on the CPU/XLA path
(``tests/test_engine_conformance.py`` pins this), which proves the carried
products never drift from ground truth through restarts, freezing, or
warm starts.

:func:`solve_stacked` is the batched entry point (what the map-step
backends in ``core/backends.py`` call); :func:`solve` is the
single-problem wrapper (a k=1 stack).

Warm starts
-----------

``solve``/``solve_stacked`` accept ``warm_x``/``warm_y`` — the previous
solution of a nearby instance.  For online re-solves (scheduler rounds,
load-balancer ticks) a warm start typically cuts iteration counts by far
more than half (``benchmarks/bench_online_resolve.py`` measures this).
With ``equilibrate=True`` the warm iterates are mapped into the scaled
space (``x/d_c``, ``y/d_r``) before iterating, so warm-starting composes
with scaling.

Algorithm: Chambolle–Pock primal–dual with
  * power-iteration estimate of ||K||,
  * step sizes tau = eta/(omega*||K||), sigma = eta*omega/||K||,
  * iterate averaging + adaptive restart to the better of {current, average}
    by KKT score (simplified PDLP restart rule),
  * primal-weight (omega) rebalancing at restarts,
  * termination on relative primal residual + relative duality gap.
"""

from __future__ import annotations

import functools
import inspect
from typing import Any, Callable, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .problem import BIG, LinearProgram


class StructuredOperator(NamedTuple):
    """Index-array form of a sparse constraint matrix K ([M, N]): each
    matvec direction gets its own gather layout — for every row, the
    column ids + coefficients that feed it (``K x``), and for every
    column, the row ids + values (``K^T y``) — so BOTH directions are pure
    gather + reduce, no scatter.

    Per side the layout is a **skew-aware two-bucket ELL**: structured LPs
    concentrate a few very wide segments (Gavel worker-cap rows and the
    epigraph ``t`` column touch every job; LB per-server load rows touch
    every shard; hot traffic edges carry many paths) among many narrow
    ones, and a uniform-width ELL would pad every narrow segment to the
    widest.  Segments wider than ~4x the median go to a separate *wide
    bucket* — an ELL over just those ``D`` segments (``w*_idx/w*_val
    [Ww, D]`` + ``w*_ids [D]`` naming which segment each bucket column
    feeds) — whose reduced results are added back with a tiny one-hot
    accumulation.  Total padded work stays ~nnz instead of
    ~n_segments * max_width.

    Arrays are nnz-major (``[..., W, M]``: padded per-segment entry count
    W on the sublane axis, segments on the lane axis) so the reduce runs
    over the leading axis while M/N stay on the 128-wide axis — what the
    Pallas kernels in ``kernels/structured_pdhg_step.py`` want.  Padding
    entries carry ``idx 0, val 0.0`` (a zero coefficient is harmless in a
    gather-multiply-add), so no validity mask is needed, duplicate
    (row, col) entries simply sum — segment-sum semantics — and empty wide
    buckets are a single zero column feeding segment 0 with 0.0.

    Wide bucket columns are kept **sorted by descending width** and each
    side carries a *fold map* (``row_fold [M]`` / ``col_fold [N]``): the
    inverse of ``w*_ids``, sending every segment to its bucket column —
    or to the one-past-the-end zero slot ``D`` when the segment is
    narrow.  The fold map turns the wide-bucket add-back into a single
    gather (``out + wide_padded[fold]``) instead of a one-hot einsum, and
    the descending sort is what lets the M-blocked full-problem engine
    (``fused_structured_full``) slice the wide arrays into contiguous
    ragged blocks with monotone widths (sliced-ELL style) so padded work
    stays ~nnz even when one bucket column is 10x wider than the median.

    Coefficient arrays default to f32 but may be stored **quantized**
    (:func:`quantize_structured`): ``bfloat16``, or ``int8`` with a
    symmetric per-bucket scale factor in ``*_scale`` ([1] f32, ``None``
    = unscaled).  Engines dequantize in-kernel and accumulate in f32.

    All leaves batch over a leading ``[k]`` sub-problem axis like every
    other ``OperatorLP`` field; :func:`stack_ops` pads per-lane
    widths/bucket sizes to the stack maximum before stacking (fold maps
    stay lane-correct: a lane's zero slot is a padded zero column of the
    stacked wide arrays).
    """

    row_idx: jnp.ndarray    # [..., Wr, M] int32 column ids feeding each row
    row_val: jnp.ndarray    # [..., Wr, M] coefficients (f32/bf16/int8)
    wrow_idx: jnp.ndarray   # [..., Ww, Dr] wide-row bucket column ids
    wrow_val: jnp.ndarray   # [..., Ww, Dr]
    wrow_ids: jnp.ndarray   # [..., Dr] int32 row fed by each bucket column
    col_idx: jnp.ndarray    # [..., Wc, N] int32 row ids feeding each column
    col_val: jnp.ndarray    # [..., Wc, N] coefficients (f32/bf16/int8)
    wcol_idx: jnp.ndarray   # [..., Wv, Dc] wide-column bucket row ids
    wcol_val: jnp.ndarray   # [..., Wv, Dc]
    wcol_ids: jnp.ndarray   # [..., Dc] int32 column fed by each bucket column
    row_fold: Optional[jnp.ndarray] = None   # [..., M] int32 bucket col or Dr
    col_fold: Optional[jnp.ndarray] = None   # [..., N] int32 bucket col or Dc
    row_scale: Optional[jnp.ndarray] = None   # [..., 1] f32 dequant scales
    wrow_scale: Optional[jnp.ndarray] = None
    col_scale: Optional[jnp.ndarray] = None
    wcol_scale: Optional[jnp.ndarray] = None

    @property
    def coef_dtype(self) -> str:
        """Storage dtype of the coefficient payload ("float32", "bfloat16"
        or "int8" — see :func:`quantize_structured`)."""
        return str(jnp.dtype(self.row_val.dtype))


def _pack_ell(seg: np.ndarray, other: np.ndarray, vals: np.ndarray,
              n_seg: int, width_mult: int = 8):
    """Pack COO entries grouped by ``seg`` into nnz-major ELL
    ``(idx [W, n_seg], val [W, n_seg])``; W rounds up to ``width_mult``
    (stable widths across re-builds keep jit caches warm)."""
    order = np.argsort(seg, kind="stable")
    s = seg[order].astype(np.int64)
    o = other[order]
    v = vals[order]
    starts = np.searchsorted(s, np.arange(n_seg))
    pos = np.arange(s.size) - starts[s] if s.size else np.zeros(0, np.int64)
    w = int(pos.max()) + 1 if s.size else 1
    w = max(1, -(-w // width_mult) * width_mult)
    idx = np.zeros((w, n_seg), np.int32)
    val = np.zeros((w, n_seg), np.float32)
    idx[pos, s] = o
    val[pos, s] = v
    return idx, val


def _pack_side(seg: np.ndarray, other: np.ndarray, vals: np.ndarray,
               n_seg: int):
    """One gather side (rows or columns) as the two-bucket ELL: segments
    wider than ``max(16, 4 * median nonzero width)`` split into the wide
    bucket, whose columns are sorted by DESCENDING width so contiguous
    column ranges have monotone widths (what the M-blocked full engine's
    ragged wide-block plan slices).  Returns
    (idx, val, widx, wval, wids, fold) where ``fold [n_seg]`` maps every
    segment to its bucket column, or to the zero slot ``d`` (one past the
    stored bucket) when narrow."""
    seg = seg.astype(np.int64)
    counts = np.bincount(seg, minlength=n_seg) if seg.size \
        else np.zeros(n_seg, np.int64)
    nz = counts[counts > 0]
    med = int(np.median(nz)) if nz.size else 1
    cap = max(16, 4 * (-(-med // 8) * 8))
    wide = np.flatnonzero(counts > cap)
    wide = wide[np.argsort(-counts[wide], kind="stable")]
    is_wide = np.isin(seg, wide)
    idx, val = _pack_ell(seg[~is_wide], other[~is_wide], vals[~is_wide],
                         n_seg)
    d = max(int(wide.size), 1)
    bucket_of = np.zeros(n_seg, np.int64)
    bucket_of[wide] = np.arange(wide.size)
    widx, wval = _pack_ell(bucket_of[seg[is_wide]], other[is_wide],
                           vals[is_wide], d)
    wids = np.zeros(d, np.int32)
    wids[: wide.size] = wide
    fold = np.full(n_seg, d, np.int32)
    fold[wide] = np.arange(wide.size)
    return idx, val, widx, wval, wids, fold


def structured_from_coo(rows, cols, vals, n_rows: int, n_cols: int,
                        coef_dtype: str = "float32") -> StructuredOperator:
    """Build a :class:`StructuredOperator` from COO triplets (numpy, at
    problem build time).  Entries may repeat (they sum) and may carry zero
    values (kept — structural zeros give shape-stable widths).
    ``coef_dtype`` selects the coefficient storage
    (:func:`quantize_structured`): "float32" (default), "bfloat16", or
    "int8" with per-bucket scale factors."""
    rows = np.asarray(rows).ravel()
    cols = np.asarray(cols).ravel()
    vals = np.asarray(vals, np.float32).ravel()
    ri, rv, wri, wrv, wrids, rfold = _pack_side(rows, cols, vals, n_rows)
    ci, cv, wci, wcv, wcids, cfold = _pack_side(cols, rows, vals, n_cols)
    j = jnp.asarray
    s = StructuredOperator(
        row_idx=j(ri), row_val=j(rv),
        wrow_idx=j(wri), wrow_val=j(wrv), wrow_ids=j(wrids),
        col_idx=j(ci), col_val=j(cv),
        wcol_idx=j(wci), wcol_val=j(wcv), wcol_ids=j(wcids),
        row_fold=j(rfold), col_fold=j(cfold))
    return quantize_structured(s, coef_dtype)


# coefficient storage dtypes quantize_structured accepts
COEF_DTYPES = ("float32", "bfloat16", "int8")


def _quantize_val(val: jnp.ndarray, dtype: str):
    """(stored, scale) for one coefficient bucket: bf16 is a plain cast
    (scale None), int8 is symmetric per-bucket — scale = max|v| / 127."""
    v = jnp.asarray(val, jnp.float32)
    if dtype == "bfloat16":
        return v.astype(jnp.bfloat16), None
    m = jnp.max(jnp.abs(v), axis=(-2, -1))
    scale = jnp.maximum(m, 1e-30) / 127.0
    q = jnp.clip(jnp.round(v / scale[..., None, None]),
                 -127, 127).astype(jnp.int8)
    return q, jnp.reshape(scale, v.shape[:-2] + (1,))


def quantize_structured(s: StructuredOperator,
                        coef_dtype: str = "int8") -> StructuredOperator:
    """Mixed-precision ELL coefficient storage (build time): re-store the
    four value arrays as ``coef_dtype`` — "bfloat16" (plain cast) or
    "int8" (symmetric per-bucket quantization, dequant scale in the
    ``*_scale`` fields) — halving / quartering the memory-bound payload
    the step kernels stream.  Engines dequantize in-kernel and accumulate
    in f32; "float32" is the identity.  Quantize from an f32 operator
    (re-quantizing a quantized one raises)."""
    if coef_dtype not in COEF_DTYPES:
        raise ValueError(f"unknown coef_dtype {coef_dtype!r}; "
                         f"expected one of {COEF_DTYPES}")
    if coef_dtype == "float32":
        return s
    if s.coef_dtype != "float32":
        raise ValueError(f"operator already stores {s.coef_dtype} "
                         "coefficients; dequantize_structured first")
    rv, rs = _quantize_val(s.row_val, coef_dtype)
    wrv, wrs = _quantize_val(s.wrow_val, coef_dtype)
    cv, cs = _quantize_val(s.col_val, coef_dtype)
    wcv, wcs = _quantize_val(s.wcol_val, coef_dtype)
    return s._replace(row_val=rv, wrow_val=wrv, col_val=cv, wcol_val=wcv,
                      row_scale=rs, wrow_scale=wrs,
                      col_scale=cs, wcol_scale=wcs)


def _dequantize_val(val: jnp.ndarray, scale: Optional[jnp.ndarray]):
    v = jnp.asarray(val, jnp.float32)
    return v if scale is None else v * scale[..., None]


def dequantize_structured(s: StructuredOperator) -> StructuredOperator:
    """Back to plain f32 coefficient storage (scales folded in, scale
    fields cleared).  Identity for f32 operators."""
    if s.coef_dtype == "float32" and s.row_scale is None:
        return s
    return s._replace(
        row_val=_dequantize_val(s.row_val, s.row_scale),
        wrow_val=_dequantize_val(s.wrow_val, s.wrow_scale),
        col_val=_dequantize_val(s.col_val, s.col_scale),
        wcol_val=_dequantize_val(s.wcol_val, s.wcol_scale),
        row_scale=None, wrow_scale=None, col_scale=None, wcol_scale=None)


def structured_to_dense(s: StructuredOperator) -> jnp.ndarray:
    """Materialise the dense K ([..., M, N]) a StructuredOperator encodes
    — from the row-side layout alone, which fully represents K (tests +
    the conformance matrix; never used on the solve path)."""
    s = dequantize_structured(s)

    def one(ri, rv, wri, wrv, wrids, n_cols):
        m = ri.shape[1]
        rows = jnp.broadcast_to(jnp.arange(m)[None, :], ri.shape)
        k0 = jnp.zeros((m, n_cols), rv.dtype)
        k0 = k0.at[rows.ravel(), ri.ravel()].add(rv.ravel())
        wrows = jnp.broadcast_to(wrids[None, :], wri.shape)
        return k0.at[wrows.ravel(), wri.ravel()].add(wrv.ravel())
    n_cols = s.col_idx.shape[-1]
    if s.row_idx.ndim == 2:
        return one(s.row_idx, s.row_val, s.wrow_idx, s.wrow_val,
                   s.wrow_ids, n_cols)
    return jax.vmap(lambda ri, rv, wri, wrv, wrids: one(
        ri, rv, wri, wrv, wrids, n_cols))(
        s.row_idx, s.row_val, s.wrow_idx, s.wrow_val, s.wrow_ids)


def scale_structured(s: StructuredOperator, d_r: jnp.ndarray,
                     d_c: jnp.ndarray) -> StructuredOperator:
    """K~ = D_r K D_c applied to the ELL payload (batched: d_r [k, M],
    d_c [k, N]).  Padded entries stay zero (0 * anything), so fold maps
    and the wide-block plan stay valid.  Quantized storage is dequantized
    first — equilibration products are not representable in int8, so the
    scaled operator degrades to f32 coefficients (the quantized payload
    is a memory-bandwidth format, not an arithmetic one)."""
    from ..kernels.ref import _bgather as bgather
    s = dequantize_structured(s)
    return s._replace(
        row_val=s.row_val * d_r[:, None, :] * bgather(d_c, s.row_idx),
        wrow_val=(s.wrow_val * bgather(d_r, s.wrow_ids)[:, None, :]
                  * bgather(d_c, s.wrow_idx)),
        col_val=s.col_val * d_c[:, None, :] * bgather(d_r, s.col_idx),
        wcol_val=(s.wcol_val * bgather(d_c, s.wcol_ids)[:, None, :]
                  * bgather(d_r, s.wcol_idx)))


class OperatorLP(NamedTuple):
    """LP in operator form.  ``data`` is whatever the K_mv/KT_mv callables
    need (dense K, index arrays, ...).  ``structured``, when present, is
    the :class:`StructuredOperator` index metadata that lets the
    ``fused_structured`` engine run the same operator as batched
    gather/segment-reduce kernels.  All leaves are batchable."""

    c: jnp.ndarray          # [N]
    q: jnp.ndarray          # [M]    rhs for K rows
    l: jnp.ndarray          # [N]
    u: jnp.ndarray          # [N]
    ineq_mask: jnp.ndarray  # [M] bool: True → dual projected >= 0
    data: Any               # operator payload pytree
    structured: Optional[StructuredOperator] = None


def dense_ops(lp: LinearProgram) -> OperatorLP:
    K, q, ineq = lp.stacked()
    return OperatorLP(c=lp.c, q=q, l=lp.l, u=lp.u, ineq_mask=ineq, data=(K,))


def dense_K_mv(data, x):
    (K,) = data
    # full f32 products: the TPU's default f32 matmul rounds to bf16
    return jnp.dot(K, x, precision=jax.lax.Precision.HIGHEST)


def dense_KT_mv(data, y):
    (K,) = data
    return jnp.dot(y, K, precision=jax.lax.Precision.HIGHEST)


def stack_ops(subs: Sequence[OperatorLP]) -> OperatorLP:
    """Stack identically-shaped sub-LPs on a leading [k] axis.  ELL widths
    (data-dependent: how congested the fullest row is in THIS lane) are
    padded to the stack maximum first, so lanes with different structured
    widths still stack; if any lane lacks metadata the whole stack drops
    it (engines must see one consistent payload)."""
    subs = list(subs)
    structs = [s.structured for s in subs]
    bare = [s._replace(structured=None) for s in subs]
    ops = jax.tree.map(lambda *xs: jnp.stack(xs), *bare)
    if any(st is None for st in structs):
        return ops
    # mixed coefficient storage cannot stack (int8 next to f32); degrade
    # the whole stack to f32 — lanes normally share one coef_dtype anyway
    if len({st.coef_dtype for st in structs}) > 1:
        structs = [dequantize_structured(st) for st in structs]

    def padto(a, shape):
        return np.pad(a, [(0, t - s) for s, t in zip(a.shape, shape)])

    # padded and stacked on the host, then placed once: per-lane device
    # pads would compile one program per distinct lane width
    stacked = {}
    for f in StructuredOperator._fields:
        vals = [getattr(st, f) for st in structs]
        if any(v is None for v in vals):
            stacked[f] = None
            continue
        vals = [np.asarray(v) for v in vals]
        shape = tuple(max(v.shape[d] for v in vals)
                      for d in range(vals[0].ndim))
        stacked[f] = jnp.asarray(np.stack([padto(v, shape) for v in vals]))
    return ops._replace(structured=StructuredOperator(**stacked))


def concat_stacks(stacks: Sequence[OperatorLP]) -> OperatorLP:
    """Concatenate already-STACKED OperatorLPs (leading ``[k_i]`` axes) into
    one ``[sum k_i]`` stack — the cross-tenant analogue of
    :func:`stack_ops`' cross-lane stacking, used by the serving dispatcher
    to coalesce concurrent tenants' sub-problem stacks into one launch.

    Structured ELL widths and wide-bucket counts (data-dependent per
    tenant) are padded to the maximum across stacks before concatenating,
    exactly like :func:`stack_ops` pads per-lane widths: padding entries
    carry ``idx 0, val 0.0`` (harmless in a gather-multiply-add) and each
    lane's fold map keeps pointing at its own zero slot, which remains a
    zero column of the widened wide arrays.  Lanes are independent in
    :func:`solve_stacked` (per-lane step sizes, restarts, termination), so
    every lane's trajectory is unchanged by who it shares a launch with.
    If any stack lacks structured metadata the result drops it; mixed
    coefficient storage dequantizes to f32 first (both mirror
    :func:`stack_ops` — the dispatcher's compatibility key never mixes
    them in practice)."""
    stacks = list(stacks)
    if len(stacks) == 1:
        return stacks[0]
    structs = [s.structured for s in stacks]
    bare = [s._replace(structured=None) for s in stacks]
    ops = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *bare)
    if any(st is None for st in structs):
        return ops
    if len({st.coef_dtype for st in structs}) > 1:
        structs = [dequantize_structured(st) for st in structs]

    def padto(a, shape):
        return jnp.pad(a, [(0, t - s) for s, t in zip(a.shape, shape)])

    merged = {}
    for f in StructuredOperator._fields:
        vals = [getattr(st, f) for st in structs]
        if any(v is None for v in vals):
            merged[f] = None
            continue
        # trailing dims (ELL widths / wide-bucket counts) pad to the max
        # across stacks; the leading [k_i] axis concatenates as-is
        trail = tuple(max(v.shape[d] for v in vals)
                      for d in range(1, vals[0].ndim))
        merged[f] = jnp.concatenate(
            [padto(v, (v.shape[0],) + trail) for v in vals])
    return ops._replace(structured=StructuredOperator(**merged))


class SolveResult(NamedTuple):
    x: jnp.ndarray
    y: jnp.ndarray
    primal_obj: jnp.ndarray
    dual_obj: jnp.ndarray
    primal_res: jnp.ndarray   # relative primal infeasibility
    gap: jnp.ndarray          # relative duality gap
    iterations: jnp.ndarray
    converged: jnp.ndarray
    n_restarts: Optional[jnp.ndarray] = None   # [k] adaptive-restart count
    diverged: Optional[jnp.ndarray] = None     # [k] lane quarantined in-loop


# --------------------------------------------------------------------------
# step engines
# --------------------------------------------------------------------------

class StepEngine(NamedTuple):
    """Batched inner-loop math for the PDHG solver (see module docstring).

    All callables take STACKED arrays (leading ``[k]`` sub-problem axis):

      K(data, x[k,N]) -> [k,M]         KT(data, y[k,M]) -> [k,N]
      forward(data, x, c, l, u, tau[k], kty[k,N]) -> (x_new, kx_new)
      backward(data, y, q, sigma[k], ineq_mask, kx_new, kx_prev)
          -> (y_new, kty_new)

    ``scale_data``, if set, rescales the operator payload for Ruiz
    equilibration (``data, d_r[k,M], d_c[k,N] -> data``); engines without
    it get their K/KT wrapped functionally instead.  ``prep``, if set,
    normalises the OperatorLP once before solving (the structured engine
    moves ``op.structured`` into ``op.data`` so every downstream consumer
    sees one payload).  ``kernels`` names what the inner loop runs as on
    this platform (``"pallas"``, ``"interpret"`` or ``"xla"`` — see
    ``kernels/ops.kernel_mode``), reported on every result.
    """

    name: str
    K: Callable
    KT: Callable
    forward: Callable
    backward: Callable
    scale_data: Optional[Callable] = None
    prep: Optional[Callable] = None
    kernels: str = "xla"


def _engine_from_matvecs(name: str, bK: Callable, bKT: Callable,
                         scale_data: Optional[Callable] = None,
                         prep: Optional[Callable] = None) -> StepEngine:
    """Build the element-wise half-step tails from batched matvecs."""

    def forward(data, x, c, l, u, tau, kty):
        x_new = jnp.clip(x - tau[:, None] * (c + kty), l, u)
        return x_new, bK(data, x_new)

    def backward(data, y, q, sigma, ineq_mask, kx_new, kx_prev):
        y_new = y + sigma[:, None] * (2.0 * kx_new - kx_prev - q)
        y_new = jnp.where(ineq_mask, jnp.maximum(y_new, 0.0), y_new)
        return y_new, bKT(data, y_new)

    return StepEngine(name, bK, bKT, forward, backward, scale_data, prep)


@functools.lru_cache(maxsize=64)
def _matvec_engine_cached(K_mv: Callable, KT_mv: Callable) -> StepEngine:
    return _engine_from_matvecs(
        "matvec", jax.vmap(K_mv, in_axes=(0, 0)),
        jax.vmap(KT_mv, in_axes=(0, 0)))


def matvec_engine(K_mv: Callable = dense_K_mv,
                  KT_mv: Callable = dense_KT_mv) -> StepEngine:
    """Generic operator engine: vmap the per-problem matvecs over the
    sub-problem axis.  Works for any structured ``data`` pytree.
    Memoized on matvec identity so repeated resolution returns ONE engine
    object per matvec pair — keeping downstream jit caches and the
    serving dispatcher's coalesce keys stable across tenants."""
    try:
        return _matvec_engine_cached(K_mv, KT_mv)
    except TypeError:
        # unhashable matvecs cannot memoize: fresh engine per call (such
        # configs never share jit caches or coalesce anyway)
        return _engine_from_matvecs(
            "matvec", jax.vmap(K_mv, in_axes=(0, 0)),
            jax.vmap(KT_mv, in_axes=(0, 0)))


@functools.lru_cache(maxsize=16)
def fused_dense_engine(kernel_backend: Optional[str] = None,
                       block_m: Optional[int] = None,
                       block_n: Optional[int] = None) -> StepEngine:
    """Dense engine over the fused Pallas kernels (``repro.kernels.ops``).

    One kernel launch covers the whole stacked batch per half-step.
    ``kernel_backend`` follows ``kernels/ops.py`` dispatch: ``None``/"auto"
    = compiled Pallas on TPU, pure-jnp reference elsewhere; "interpret" and
    "xla" force the Pallas interpreter / the reference.  Cached so repeated
    calls return the same object (keeps downstream jit caches warm).
    """
    from ..kernels import ops as kops

    kw: dict = dict(backend=kernel_backend)
    if block_m is not None:
        kw["block_m"] = block_m
    if block_n is not None:
        kw["block_n"] = block_n

    def K(data, x):
        return kops.bmatvec(data[0], x, **kw)

    def KT(data, y):
        return kops.bmatvec_t(data[0], y, **kw)

    def forward(data, x, c, l, u, tau, kty):
        return kops.fused_forward_step(data[0], x, c, l, u, tau, kty, **kw)

    def backward(data, y, q, sigma, ineq_mask, kx_new, kx_prev):
        return kops.fused_backward_step(data[0], y, q, sigma, ineq_mask,
                                        kx_new, kx_prev, **kw)

    def scale_data(data, d_r, d_c):
        (K_,) = data
        return (K_ * d_r[..., :, None] * d_c[..., None, :],)

    return StepEngine("fused", K, KT, forward, backward, scale_data,
                      kernels=kops.kernel_mode("dense", kernel_backend))


@functools.lru_cache(maxsize=16)
def fused_structured_engine(
        kernel_backend: Optional[str] = None,
        row_plan: tuple = (), col_plan: tuple = ()) -> StepEngine:
    """Structured engine over the batched gather/segment-reduce forms
    (``kernels/ops.py`` dispatch: the XLA ``take_along_axis`` reference on
    every platform, the Pallas kernels of ``structured_pdhg_step.py`` when
    forced).  One launch per half-step across the whole k-lane stack.
    ``row_plan`` / ``col_plan`` are static ragged wide-block plans (see
    :func:`fused_structured_full_engine`) taken across all lanes; the
    XLA form applies them, the Pallas lane kernels ignore them.  Requires
    ``op.structured``; ``prep`` moves it into ``op.data`` so the payload
    flows through backends/jit as ordinary traced arrays."""
    from ..kernels import ops as kops

    kw: dict = dict(backend=kernel_backend)

    def K(data, x):
        return kops.smatvec(data, x, plan=row_plan)

    def KT(data, y):
        return kops.smatvec_t(data, y, plan=col_plan)

    def forward(data, x, c, l, u, tau, kty):
        return kops.structured_forward_step(data, x, c, l, u, tau, kty,
                                            plan=row_plan, **kw)

    def backward(data, y, q, sigma, ineq_mask, kx_new, kx_prev):
        return kops.structured_backward_step(data, y, q, sigma, ineq_mask,
                                             kx_new, kx_prev, plan=col_plan,
                                             **kw)

    def prep(op: OperatorLP) -> OperatorLP:
        # the lane kernels have no dequant path — quantized payloads
        # degrade to f32 here (only fused_structured_full streams them)
        return op._replace(data=dequantize_structured(op.structured),
                           structured=None)

    return StepEngine("fused_structured", K, KT, forward, backward,
                      scale_structured, prep,
                      kernels=kops.kernel_mode("gather", kernel_backend))


@functools.lru_cache(maxsize=16)
def fused_structured_full_engine(
        kernel_backend: Optional[str] = None,
        row_plan: tuple = (), col_plan: tuple = ()) -> StepEngine:
    """Single-lane M-blocked streaming engine for the **full** problem
    (``kernels/structured_pdhg_step.py`` full-kernel family via
    ``kernels/ops.py`` dispatch).  The lane kernels assume a whole lane's
    ELL payload fits in VMEM; this engine tiles the nnz-major ``[W, M]``
    arrays into VMEM-sized M-blocks, streams partial gather/reduces per
    block and folds wide-bucket contributions across blocks through the
    fold map — so the unpartitioned k=1 baseline runs the same no-scatter
    path as POP lanes.

    ``row_plan`` / ``col_plan`` are static ragged wide-block plans: tuples
    of ``(c0, c1, wb)`` — slice bucket columns ``[c0, c1)`` at width
    ``wb`` — computed by :func:`resolve_engine` from the *concrete*
    operator (outside jit) against the descending-width sort
    ``_pack_side`` guarantees.  The slices view the one uniform wide
    array, so equilibration scaling composes with the plan for free.
    ``prep`` moves ``op.structured`` into ``op.data`` (quantized payloads
    flow through — the full kernels dequantize in-kernel)."""
    from ..kernels import ops as kops

    kw: dict = dict(backend=kernel_backend)

    def K(data, x):
        return kops.smatvec_full(data, x, plan=row_plan)

    def KT(data, y):
        return kops.smatvec_t_full(data, y, plan=col_plan)

    def forward(data, x, c, l, u, tau, kty):
        return kops.structured_full_forward_step(
            data, x, c, l, u, tau, kty, plan=row_plan, **kw)

    def backward(data, y, q, sigma, ineq_mask, kx_new, kx_prev):
        return kops.structured_full_backward_step(
            data, y, q, sigma, ineq_mask, kx_new, kx_prev,
            plan=col_plan, **kw)

    def prep(op: OperatorLP) -> OperatorLP:
        return op._replace(data=op.structured, structured=None)

    return StepEngine("fused_structured_full", K, KT, forward, backward,
                      scale_structured, prep,
                      kernels=kops.kernel_mode("gather", kernel_backend))


# auto picks fused_structured_full only above this many stored wide-bucket
# elements: below it the one-hot fold is cheap and the lane kernels win
FULL_ENGINE_MIN_WIDE_ELEMS = 65_536
# column chunk the ragged wide-block plan is quantised to
WIDE_BLOCK_COLS = 128


def _is_single_lane(op: OperatorLP) -> bool:
    return op.c.ndim == 1 or op.c.shape[0] == 1


def _wide_elems(s: StructuredOperator) -> int:
    return (s.wrow_idx.shape[-2] * s.wrow_idx.shape[-1]
            + s.wcol_idx.shape[-2] * s.wcol_idx.shape[-1])


@jax.jit
def _effective_widths(wval):
    """[D] effective width of each wide-bucket column, the most over all
    lanes: the index of its last nonzero + 1 (0 if empty)."""
    ww, d = wval.shape[-2:]
    depth = jnp.arange(1, ww + 1, dtype=jnp.int32)[:, None]
    last = jnp.max(jnp.where(wval != 0, depth, 0), axis=-2)
    return jnp.max(last.reshape(-1, d), axis=0)


def _wide_block_plan(wval) -> tuple:
    """Static ragged plan ``((c0, c1, wb), ...)`` over a wide bucket's
    descending-width columns: chunks of :data:`WIDE_BLOCK_COLS` columns,
    each sliced to its own max effective width over all lanes (from
    ``val != 0`` — exact, since zero coefficients contribute nothing)
    rounded up to the f32 sublane multiple.  Needs a concrete array; on
    tracers (a user jitting ``solve_stacked`` around resolution) falls
    back to one full-width block — correct, just unsliced."""
    if isinstance(wval, jax.core.Tracer):
        ww = wval.shape[-2]
        return ((0, wval.shape[-1], ww),)
    ww, d = wval.shape[-2:]
    # deliberately host-side: the plan must be static (baked into the
    # lru-cached engine), and the Tracer guard above already routed any
    # traced value away — what reaches here is concrete by construction;
    # only the [D] widths leave the device
    counts = np.asarray(  # popcheck: disable=host-sync-in-hot-path
        _effective_widths(wval))
    plan = []
    for c0 in range(0, d, WIDE_BLOCK_COLS):
        c1 = min(c0 + WIDE_BLOCK_COLS, d)
        wmax = (int(counts[c0:c1].max())  # popcheck: disable=host-sync-in-hot-path
                if c1 > c0 else 0)
        wb = min(max(8, -(-wmax // 8) * 8), ww)
        plan.append((c0, c1, wb))
    return tuple(plan) if plan else ((0, d, ww),)


def is_dense_ops(op: OperatorLP) -> bool:
    """True iff ``op.data`` is a single dense [..., M, N] constraint matrix
    (the layout :func:`dense_ops` produces) — the fused engine's requirement."""
    leaves = jax.tree.leaves(op.data)
    if len(leaves) != 1:
        return False
    K = leaves[0]
    return (K.ndim == op.c.ndim + 1
            and K.shape[-1] == op.c.shape[-1]
            and K.shape[-2] == op.q.shape[-1])


def select_engine(op: OperatorLP, K_mv: Callable = dense_K_mv,
                  KT_mv: Callable = dense_KT_mv) -> str:
    """``engine="auto"`` rule: a ``preferred_engine`` attribute on the
    problem's ``K_mv`` wins outright (the domain measured its own best —
    load balancing pins ``matvec`` because its operator is a dense
    [n, S] block where the gather-ELL path does ~2x the flops); otherwise
    fused needs dense data AND the dense matvecs AND a TPU (elsewhere XLA
    fuses the reference path just as well); operators carrying
    :class:`StructuredOperator` index metadata take the structured-fused
    engine (gather/segment-reduce, no scatters, one launch per half-step —
    measured 2-18x over vmapped segment-sum matvecs on the gather-shaped
    domains); **single-lane** structured operators whose wide buckets are
    large (>= :data:`FULL_ENGINE_MIN_WIDE_ELEMS` stored elements) take the
    M-blocked streaming ``fused_structured_full`` engine instead — the
    ``solve_full`` baseline at paper scale, where the lane path's
    uniform-width padding and one-hot fold dominate (measured 13x on the
    traffic matvec pair at 3000 demands); everything else takes
    ``matvec``."""
    pref = getattr(K_mv, "preferred_engine", None)
    if pref is not None:
        return pref
    dense = (K_mv is dense_K_mv and KT_mv is dense_KT_mv and is_dense_ops(op))
    if dense and jax.default_backend() == "tpu":
        return "fused"
    if op.structured is not None:
        s = op.structured
        if (_is_single_lane(op) and s.row_fold is not None
                and _wide_elems(s) >= FULL_ENGINE_MIN_WIDE_ELEMS):
            return "fused_structured_full"
        return "fused_structured"
    return "matvec"


# the engine spec strings resolve_engine accepts (besides a StepEngine
# object) — what ExecConfig validates at construction
ENGINE_NAMES = ("auto", "matvec", "fused", "fused_structured",
                "fused_structured_full")


def engine_name(engine: Union[str, "StepEngine"]) -> str:
    """Printable name of an engine spec (a resolved StepEngine or a str)."""
    return engine if isinstance(engine, str) else engine.name


# kernel family behind each named engine (``kernels/ops.kernel_mode``)
_ENGINE_FAMILY = {"matvec": "matvec", "fused": "dense",
                  "fused_structured": "gather",
                  "fused_structured_full": "gather"}


def engine_kernels(engine: Union[str, "StepEngine"]) -> str:
    """What a resolved engine's inner loop runs as here: ``"pallas"``,
    ``"interpret"`` or ``"xla"`` (a named engine with default kernels)."""
    if isinstance(engine, StepEngine):
        return engine.kernels
    from ..kernels import ops as kops
    return kops.kernel_mode(_ENGINE_FAMILY[engine])


def resolve_engine(engine: Union[None, str, StepEngine], op: OperatorLP,
                   K_mv: Callable = dense_K_mv,
                   KT_mv: Callable = dense_KT_mv) -> StepEngine:
    """Normalise an engine spec (None/"auto"/"matvec"/"fused"/
    "fused_structured"/"fused_structured_full"/StepEngine).  For the
    structured engines this is also where the static ragged wide-block
    plans are
    computed — call it with a *concrete* operator (``backends.resolve_exec``
    does, before anything is jitted) so the plan can inspect values."""
    if isinstance(engine, StepEngine):
        return engine
    if engine is None or engine == "auto":
        engine = select_engine(op, K_mv, KT_mv)
    if engine == "matvec":
        return matvec_engine(K_mv, KT_mv)
    if engine == "fused":
        if not is_dense_ops(op):
            raise ValueError(
                "engine='fused' needs dense operator data (op.data == (K,) "
                "with K [..., M, N]); structured operators use "
                "engine='matvec' or 'fused_structured'")
        return fused_dense_engine()
    if engine == "fused_structured":
        if op.structured is None:
            raise ValueError(
                "engine='fused_structured' needs op.structured "
                "(StructuredOperator index metadata attached by the "
                "problem's build_sub); operators without it use "
                "engine='matvec'")
        s = op.structured
        return fused_structured_engine(
            row_plan=_wide_block_plan(s.wrow_val),
            col_plan=_wide_block_plan(s.wcol_val))
    if engine == "fused_structured_full":
        s = op.structured
        if s is None or s.row_fold is None:
            raise ValueError(
                "engine='fused_structured_full' needs op.structured with "
                "fold maps (operators built by structured_from_coo); "
                "operators without it use engine='matvec'")
        if not _is_single_lane(op):
            raise ValueError(
                "engine='fused_structured_full' streams the single-lane "
                "full problem (k=1); stacked sub-problems use "
                "engine='fused_structured'")
        return fused_structured_full_engine(
            row_plan=_wide_block_plan(s.wrow_val),
            col_plan=_wide_block_plan(s.wcol_val))
    raise ValueError(f"unknown engine {engine!r}; expected 'auto', "
                     "'matvec', 'fused', 'fused_structured', "
                     "'fused_structured_full', or a StepEngine")


# --------------------------------------------------------------------------
# scaling helpers — the ONE place BIG-sentinel bounds handling lives, shared
# by the probe-based path (solve(equilibrate=True)) and dense ruiz_equilibrate
# --------------------------------------------------------------------------

def scale_operator(op: OperatorLP, d_r: jnp.ndarray, d_c: jnp.ndarray,
                   data: Any = None) -> OperatorLP:
    """Apply diagonal scalings K~ = D_r K D_c to the LP fields.

    BIG-sentinel bounds (|l| or |u| >= BIG/2 — "effectively free") stay
    untouched so padded/free variables keep their infinite box after
    scaling.  ``data`` replaces the operator payload when the caller has a
    scaled one (dense K, scaled ELL); by default the payload is left alone
    and the matvecs are expected to be wrapped instead.  Any
    ``op.structured`` metadata is DROPPED — it describes the unscaled
    operator (the structured engine's ``prep`` has already moved its
    payload into ``data`` by the time scaling runs).
    """
    keep_l = jnp.abs(op.l) >= 0.5 * BIG
    keep_u = jnp.abs(op.u) >= 0.5 * BIG
    return OperatorLP(
        c=op.c * d_c, q=op.q * d_r,
        l=jnp.where(keep_l, op.l, op.l / d_c),
        u=jnp.where(keep_u, op.u, op.u / d_c),
        ineq_mask=op.ineq_mask,
        data=op.data if data is None else data,
        structured=None)


def scale_warm_start(x: jnp.ndarray, y: jnp.ndarray, d_r, d_c):
    """Original-space iterates -> scaled space (inverse of unscale)."""
    return x / d_c, y / d_r


def unscale_solution(x: jnp.ndarray, y: jnp.ndarray, d_r, d_c):
    """Scaled-space iterates -> original space: x = d_c x~, y = d_r y~."""
    return d_c * x, d_r * y


# --------------------------------------------------------------------------
# internals (all batched over the leading [k] sub-problem axis)
# --------------------------------------------------------------------------

def _vnorm(a: jnp.ndarray) -> jnp.ndarray:
    """Per-sub-problem 2-norm: [k, n] -> [k]."""
    return jnp.linalg.norm(a, axis=-1)


def _bcast(cond: jnp.ndarray, like: jnp.ndarray) -> jnp.ndarray:
    """Right-pad a [k] predicate with singleton axes to broadcast against
    ``like`` ([k] or [k, n])."""
    return cond.reshape(cond.shape + (1,) * (like.ndim - cond.ndim))


def _power_iteration(engine: StepEngine, data, k: int, n_var: int,
                     iters: int = 30):
    """||K||_2 per lane via power iteration on K^T K (deterministic start)."""
    v0 = jnp.full((k, n_var), 1.0 / jnp.sqrt(n_var), jnp.float32)

    def body(_, v):
        w = engine.KT(data, engine.K(data, v))
        return w / (_vnorm(w)[:, None] + 1e-30)

    v = jax.lax.fori_loop(0, iters, body, v0)
    return jnp.sqrt(_vnorm(engine.KT(data, engine.K(data, v)))) + 1e-12


def _kkt_from_products(op: OperatorLP, x, y, kx, kty):
    """(primal_res_rel, gap_rel, primal_obj, dual_obj), each [k], from the
    already-materialised products ``kx = K x`` / ``kty = K^T y``.  The ONE
    place the KKT formulas live — the in-loop path feeds carried products,
    :func:`_kkt` feeds fresh operator passes, and both must agree bit-level
    when the products do."""
    resid = kx - op.q
    prim_viol = jnp.where(op.ineq_mask, jnp.maximum(resid, 0.0), resid)
    # padded rows carry q = BIG — exclude them from the relative denominator
    q_eff = jnp.where(jnp.abs(op.q) >= 0.5 * BIG, 0.0, op.q)
    prim_res = _vnorm(prim_viol) / (1.0 + _vnorm(q_eff))

    r = op.c + kty                                    # reduced costs
    p_obj = jnp.sum(op.c * x, axis=-1)
    # g(y) = -q.y + sum_i min(l_i r_i, u_i r_i); BIG bounds act as -inf penalty
    d_obj = (-jnp.sum(op.q * y, axis=-1)
             + jnp.sum(jnp.minimum(op.l * r, op.u * r), axis=-1))
    gap = jnp.abs(p_obj - d_obj) / (1.0 + jnp.abs(p_obj) + jnp.abs(d_obj))
    return prim_res, gap, p_obj, d_obj


def _kkt(op: OperatorLP, engine: StepEngine, x, y):
    """KKT scores via fresh operator passes (standalone reference; also the
    final original-space report)."""
    return _kkt_from_products(op, x, y, engine.K(op.data, x),
                              engine.KT(op.data, y))


class _State(NamedTuple):
    x: jnp.ndarray
    y: jnp.ndarray
    kx: jnp.ndarray           # carried K x      (current iterate's product)
    kty: jnp.ndarray          # carried K^T y
    x_sum: jnp.ndarray
    y_sum: jnp.ndarray
    kx_sum: jnp.ndarray       # running product sums: K x_avg = kx_sum/avg_n
    kty_sum: jnp.ndarray      # (linearity of K — averages cost no passes)
    avg_n: jnp.ndarray        # [k] iterations accumulated since restart
    x_anchor: jnp.ndarray     # iterate at last restart (for omega update)
    y_anchor: jnp.ndarray
    omega: jnp.ndarray        # [k] primal weight
    last_score: jnp.ndarray   # [k] KKT score at last restart (decay test)
    it: jnp.ndarray           # [k]
    done: jnp.ndarray         # [k]
    n_restarts: jnp.ndarray   # [k]
    prim_res: jnp.ndarray
    gap: jnp.ndarray
    best_score: jnp.ndarray   # [k] best KKT score seen (divergence baseline)
    diverged: jnp.ndarray     # [k] lane frozen by the divergence guard


def _equilibrate(engine: StepEngine, op: OperatorLP,
                 iters: int = 2, n_probes: int = 4):
    """Operator-form Ruiz equilibration (EXPERIMENTS.md §Perf hillclimb 3):
    per-lane (d_r, d_c) diagonal scalings estimated purely through matvec
    probes (Hutchinson: with Rademacher v, E[(Kv)_i^2] = squared row norms;
    columns dual) — works for ANY structured operator, not just dense K.
    The same probe vectors are shared across the k lanes."""
    n_var = op.c.shape[-1]
    n_con = op.q.shape[-1]
    d_r = jnp.ones_like(op.q)
    d_c = jnp.ones_like(op.c)
    key = jax.random.PRNGKey(7)
    for i in range(iters):
        kr, kc = jax.random.split(jax.random.fold_in(key, i))
        vs = jax.random.rademacher(kr, (n_probes, n_var), jnp.float32)
        rows = jnp.mean(jax.vmap(
            lambda v: jnp.square(d_r * engine.K(op.data, d_c * v)))(vs), axis=0)
        us = jax.random.rademacher(kc, (n_probes, n_con), jnp.float32)
        cols = jnp.mean(jax.vmap(
            lambda u: jnp.square(d_c * engine.KT(op.data, d_r * u)))(us), axis=0)
        rn, cn = jnp.sqrt(rows), jnp.sqrt(cols)
        d_r = d_r / jnp.sqrt(jnp.where(rn > 1e-8, rn, 1.0))
        d_c = d_c / jnp.sqrt(jnp.where(cn > 1e-8, cn, 1.0))
    return d_r, d_c


def _scoped(name: str):
    """Decorator: each call runs under a fresh ``jax.named_scope(name)``,
    which names the operations it stages in the compiled program (a scope
    object used as a decorator itself keeps its state on the one object,
    which threads tracing at once would share)."""
    def deco(fn):
        @functools.wraps(fn)
        def scoped(*args, **kw):
            with jax.named_scope(name):
                return fn(*args, **kw)
        return scoped
    return deco


@_scoped("pdhg.solve_stacked")
def solve_stacked(
    op: OperatorLP,
    engine: Union[None, str, StepEngine] = None,
    K_mv: Callable = dense_K_mv,
    KT_mv: Callable = dense_KT_mv,
    *,
    max_iters: int = 20_000,
    check_every: int = 40,
    tol_primal: float = 1e-4,
    tol_gap: float = 1e-4,
    eta: float = 0.9,
    omega0: float = 1.0,
    equilibrate: bool = False,
    warm_x: Optional[jnp.ndarray] = None,
    warm_y: Optional[jnp.ndarray] = None,
    warm_mask: Optional[jnp.ndarray] = None,
    kkt: str = "inloop",
    divergence_ratio: float = 1e4,
) -> SolveResult:
    """Solve a STACK of k LPs at once (every ``op`` leaf has a leading [k]
    axis; the result carries the same axis).  This is the map-step core:
    one fori/while loop drives all k sub-problems with per-lane step sizes,
    restarts and termination, so the fused engines can hand the whole batch
    to single kernel launches.  Fully traceable.

    ``warm_mask`` ([k] bool) gates the warm start per lane: False lanes
    start cold even when ``warm_x``/``warm_y`` are given.  This is how
    churn-aware remapped warm starts (``core/plan.py``) cold-start lanes
    that matched no previous entity — a ``jnp.where`` on data, not a
    Python-level branch, so all lanes share one jitted solve.

    ``kkt="inloop"`` (default) computes convergence checks entirely from
    the products the half-steps already materialised — zero extra operator
    passes per check.  ``kkt="standalone"`` re-derives the current
    candidate's products with fresh K/K^T passes each check (2 extra
    applications per chunk): the verification reference that must match
    the in-loop path bit-level on the CPU/XLA path.

    Divergence quarantine: a lane whose KKT score goes non-finite (NaN/inf
    iterates, e.g. from a poisoned warm start) or exceeds
    ``divergence_ratio`` times the best score it has seen is frozen in
    place and reported in ``SolveResult.diverged`` ([k] bool).  The guard
    is carried as loop data exactly like ``done`` — no host sync, no
    retrace — and healthy batch peers keep iterating.  Diverged lanes
    report ``converged=False``; callers (``service.PopSession``) quarantine
    the warm state and cold-restart only those lanes.
    """
    if kkt not in ("inloop", "standalone"):
        raise ValueError(f"unknown kkt mode {kkt!r}; "
                         "expected 'inloop' or 'standalone'")
    eng = resolve_engine(engine, op, K_mv, KT_mv)
    if eng.prep is not None:
        op = eng.prep(op)
    k = op.c.shape[0]
    n_var = op.c.shape[-1]

    op_run, eng_run = op, eng
    if equilibrate:
        d_r, d_c = _equilibrate(eng, op)
        if eng.scale_data is not None:
            op_run = scale_operator(op, d_r, d_c,
                                    data=eng.scale_data(op.data, d_r, d_c))
        else:
            op_run = scale_operator(op, d_r, d_c)
            eng_run = _engine_from_matvecs(
                eng.name + "_scaled",
                lambda data, x: d_r * eng.K(data, d_c * x),
                lambda data, y: d_c * eng.KT(data, d_r * y))
        # warm iterates arrive in ORIGINAL space — map into scaled space
        if warm_x is not None:
            warm_x = warm_x / d_c
        if warm_y is not None:
            warm_y = warm_y / d_r

    knorm = _power_iteration(eng_run, op_run.data, k, n_var)   # [k]

    cold_x = jnp.clip(jnp.zeros_like(op_run.c), op_run.l, op_run.u)
    cold_y = jnp.zeros_like(op_run.q)
    x0 = cold_x if warm_x is None else jnp.asarray(warm_x, op_run.c.dtype)
    y0 = cold_y if warm_y is None else jnp.asarray(warm_y, op_run.q.dtype)
    if warm_mask is not None and (warm_x is not None or warm_y is not None):
        m = jnp.asarray(warm_mask, bool)[:, None]
        x0 = jnp.where(m, x0, cold_x)
        y0 = jnp.where(m, y0, cold_y)
    # seed the carried products (once per solve; every later refresh rides
    # inside a half-step)
    kx0 = eng_run.K(op_run.data, x0)
    kty0 = eng_run.KT(op_run.data, y0)

    def check(state: _State, x, y, kx, kty, xs, ys, kxs, ktys) -> _State:
        """Convergence check and restart after a chunk of iterations."""
        avg_n = state.avg_n + check_every

        # ---- candidate = better of {current, running average} ------------
        # products for the current candidate are carried (in-loop mode) or
        # recomputed with fresh operator passes (standalone verification
        # mode); the average candidate's products are ALWAYS the running
        # sums — K(x_avg) == avg(K x_i) by linearity, so the averages never
        # cost a pass in either mode.
        if kkt == "standalone":
            kx_cur = eng_run.K(op_run.data, x)
            kty_cur = eng_run.KT(op_run.data, y)
        else:
            kx_cur, kty_cur = kx, kty
        nrm = avg_n[:, None]
        x_avg, y_avg = xs / nrm, ys / nrm
        kx_avg, kty_avg = kxs / nrm, ktys / nrm
        pr_c, gap_c, _, _ = _kkt_from_products(op_run, x, y, kx_cur, kty_cur)
        pr_a, gap_a, _, _ = _kkt_from_products(op_run, x_avg, y_avg,
                                               kx_avg, kty_avg)
        score_c = pr_c + gap_c
        score_a = pr_a + gap_a
        use_avg = score_a < score_c                # [k]
        sel = use_avg[:, None]
        x_r = jnp.where(sel, x_avg, x)
        y_r = jnp.where(sel, y_avg, y)
        kx_r = jnp.where(sel, kx_avg, kx_cur)
        kty_r = jnp.where(sel, kty_avg, kty_cur)
        pr = jnp.where(use_avg, pr_a, pr_c)
        gap = jnp.where(use_avg, gap_a, gap_c)
        score = jnp.minimum(score_a, score_c)

        # ---- divergence guard: non-finite score, or blow-up past the best
        # score this lane ever reached.  best_score starts at +inf so the
        # ratio test cannot fire before a finite score exists.  Pure data —
        # the lane freezes via the same mechanism as `done`.
        blown = (~jnp.isfinite(score)) | (
            score > divergence_ratio * jnp.maximum(state.best_score, 1e-12))
        diverged = state.diverged | (blown & ~state.done)
        best_score = jnp.minimum(
            state.best_score, jnp.where(jnp.isfinite(score), score, jnp.inf))

        # ---- adaptive restart: only on sufficient KKT decay ---------------
        # (restarting every chunk kills PDHG momentum; PDLP-style decay test)
        restart = (score < 0.4 * state.last_score) | (avg_n >= 16 * check_every)

        # ---- primal weight update at restarts (PDLP eq. 10, smoothed) -----
        dx = _vnorm(x_r - state.x_anchor)
        dy = _vnorm(y_r - state.y_anchor)
        safe = (dx > 1e-12) & (dy > 1e-12)
        ratio = jnp.where(safe, dy / jnp.maximum(dx, 1e-12), 1.0)
        omega_new = jnp.exp(
            0.5 * jnp.log(jnp.clip(ratio, 1e-4, 1e4)) + 0.5 * jnp.log(state.omega)
        )

        conv = (pr < tol_primal) & (gap < tol_gap) & ~state.diverged
        done = state.done | conv

        def pick(on_restart, no_restart):
            return jnp.where(_bcast(restart, on_restart), on_restart, no_restart)

        # freeze finished AND quarantined lanes: batch peers keep going
        frozen = state.done | state.diverged

        def keep(new, old):
            return jnp.where(_bcast(frozen, new), old, new)

        return _State(
            x=keep(pick(x_r, x), state.x),
            y=keep(pick(y_r, y), state.y),
            # the restarted point's products restart with it (the averaged
            # products ARE the average point's products, by linearity)
            kx=keep(pick(kx_r, kx_cur), state.kx),
            kty=keep(pick(kty_r, kty_cur), state.kty),
            x_sum=keep(pick(jnp.zeros_like(xs), xs), state.x_sum),
            y_sum=keep(pick(jnp.zeros_like(ys), ys), state.y_sum),
            kx_sum=keep(pick(jnp.zeros_like(kxs), kxs), state.kx_sum),
            kty_sum=keep(pick(jnp.zeros_like(ktys), ktys), state.kty_sum),
            avg_n=keep(pick(jnp.zeros_like(avg_n), avg_n), state.avg_n),
            x_anchor=keep(pick(x_r, state.x_anchor), state.x_anchor),
            y_anchor=keep(pick(y_r, state.y_anchor), state.y_anchor),
            omega=keep(pick(omega_new, state.omega), state.omega),
            last_score=keep(pick(score, state.last_score), state.last_score),
            it=state.it + jnp.where(frozen, 0, check_every),
            done=done,
            n_restarts=state.n_restarts + jnp.where(
                frozen | ~restart, 0, 1).astype(jnp.int32),
            prim_res=keep(pr, state.prim_res), gap=keep(gap, state.gap),
            best_score=keep(best_score, state.best_score),
            diverged=diverged,
        )

    def chunk(state: _State) -> _State:
        tau = eta / (state.omega * knorm)          # [k]
        sigma = eta * state.omega / knorm          # [k]

        def one_iter(_, carry):
            x, y, kx, kty, xs, ys, kxs, ktys = carry
            with jax.named_scope("pdhg.forward"):
                x_new, kx_new = eng_run.forward(op_run.data, x, op_run.c,
                                                op_run.l, op_run.u, tau, kty)
            with jax.named_scope("pdhg.backward"):
                y_new, kty_new = eng_run.backward(op_run.data, y, op_run.q,
                                                  sigma, op_run.ineq_mask,
                                                  kx_new, kx)
            return (x_new, y_new, kx_new, kty_new,
                    xs + x_new, ys + y_new, kxs + kx_new, ktys + kty_new)

        x, y, kx, kty, xs, ys, kxs, ktys = jax.lax.fori_loop(
            0, check_every, one_iter,
            (state.x, state.y, state.kx, state.kty,
             state.x_sum, state.y_sum, state.kx_sum, state.kty_sum),
        )
        with jax.named_scope("pdhg.check"):
            return check(state, x, y, kx, kty, xs, ys, kxs, ktys)

    init = _State(
        x=x0, y=y0, kx=kx0, kty=kty0,
        x_sum=jnp.zeros_like(x0), y_sum=jnp.zeros_like(y0),
        kx_sum=jnp.zeros_like(kx0), kty_sum=jnp.zeros_like(kty0),
        avg_n=jnp.zeros((k,), jnp.float32),
        x_anchor=x0, y_anchor=y0,
        omega=jnp.full((k,), omega0, jnp.float32),
        last_score=jnp.full((k,), jnp.inf),
        it=jnp.zeros((k,), jnp.int32),
        done=jnp.zeros((k,), bool),
        n_restarts=jnp.zeros((k,), jnp.int32),
        prim_res=jnp.full((k,), jnp.inf), gap=jnp.full((k,), jnp.inf),
        best_score=jnp.full((k,), jnp.inf),
        diverged=jnp.zeros((k,), bool),
    )

    state = jax.lax.while_loop(
        lambda s: jnp.any((~s.done) & (~s.diverged) & (s.it < max_iters)),
        chunk, init,
    )

    x_fin, y_fin = state.x, state.y
    if equilibrate:
        # report in ORIGINAL space
        x_fin, y_fin = unscale_solution(x_fin, y_fin, d_r, d_c)
    with jax.named_scope("pdhg.kkt"):
        pr, gap, p_obj, d_obj = _kkt(op, eng, x_fin, y_fin)
    return SolveResult(
        x=x_fin, y=y_fin, primal_obj=p_obj, dual_obj=d_obj,
        primal_res=pr, gap=gap, iterations=state.it, converged=state.done,
        n_restarts=state.n_restarts, diverged=state.diverged,
    )


# the keyword names a solver_kw dict may carry (everything solve_stacked
# takes except the operator/engine/warm plumbing, which the pipeline
# threads itself) — what ExecConfig validates at construction
SOLVER_KW_NAMES = frozenset(
    name for name, p in inspect.signature(solve_stacked).parameters.items()
    if p.kind is inspect.Parameter.KEYWORD_ONLY
    and not name.startswith("warm_"))


def solve(
    op: OperatorLP,
    K_mv: Callable = dense_K_mv,
    KT_mv: Callable = dense_KT_mv,
    *,
    max_iters: int = 20_000,
    check_every: int = 40,
    tol_primal: float = 1e-4,
    tol_gap: float = 1e-4,
    eta: float = 0.9,
    omega0: float = 1.0,
    equilibrate: bool = False,
    warm_x: Optional[jnp.ndarray] = None,
    warm_y: Optional[jnp.ndarray] = None,
    warm_mask: Optional[jnp.ndarray] = None,
    engine: Union[None, str, StepEngine] = "matvec",
    kkt: str = "inloop",
    divergence_ratio: float = 1e4,
) -> SolveResult:
    """Solve one LP: a k=1 stack through :func:`solve_stacked`.  Fully
    traceable; vmap over a batched ``op`` for POP (or better, hand the
    whole stack to ``solve_stacked`` / ``backends.solve_map``)."""
    opb = jax.tree.map(lambda a: jnp.asarray(a)[None], op)
    wx = None if warm_x is None else jnp.asarray(warm_x)[None]
    wy = None if warm_y is None else jnp.asarray(warm_y)[None]
    wm = None if warm_mask is None else jnp.asarray(warm_mask).reshape((1,))
    res = solve_stacked(
        opb, engine=engine, K_mv=K_mv, KT_mv=KT_mv,
        max_iters=max_iters, check_every=check_every,
        tol_primal=tol_primal, tol_gap=tol_gap, eta=eta, omega0=omega0,
        equilibrate=equilibrate, warm_x=wx, warm_y=wy, warm_mask=wm, kkt=kkt,
        divergence_ratio=divergence_ratio)
    return jax.tree.map(lambda a: a[0], res)


# --------------------------------------------------------------------------
# Ruiz equilibration (dense path) — first-order methods live or die by
# conditioning; diagonal rescaling cuts PDHG iteration counts by 10-100x.
# Bounds/rhs handling is shared with the probe path via scale_operator.
# --------------------------------------------------------------------------

def ruiz_equilibrate(op: OperatorLP, iters: int = 8):
    """Return (scaled_op, d_row, d_col) with K~ = D_r K D_c equilibrated.

    Recover original-space solutions as  x = d_col * x~,  y = d_row * y~
    (:func:`unscale_solution`).  Dense-data only (needs explicit row/col
    norms); the probe-based path inside ``solve(equilibrate=True)`` covers
    structured operators.
    """
    (K,) = op.data
    d_r = jnp.ones(K.shape[0])
    d_c = jnp.ones(K.shape[1])

    def body(_, carry):
        d_r, d_c = carry
        Ks = K * d_r[:, None] * d_c[None, :]
        rn = jnp.sqrt(jnp.max(jnp.abs(Ks), axis=1))
        cn = jnp.sqrt(jnp.max(jnp.abs(Ks), axis=0))
        d_r = d_r / jnp.where(rn > 1e-12, rn, 1.0)
        d_c = d_c / jnp.where(cn > 1e-12, cn, 1.0)
        return d_r, d_c

    d_r, d_c = jax.lax.fori_loop(0, iters, body, (d_r, d_c))
    Ks = K * d_r[:, None] * d_c[None, :]
    return scale_operator(op, d_r, d_c, data=(Ks,)), d_r, d_c


# --------------------------------------------------------------------------
# convenience wrappers
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("max_iters", "tol_primal", "tol_gap"))
def solve_dense(lp: LinearProgram, max_iters: int = 20_000,
                tol_primal: float = 1e-4, tol_gap: float = 1e-4) -> SolveResult:
    op = dense_ops(lp)
    sop, d_r, d_c = ruiz_equilibrate(op)
    res = solve(sop, dense_K_mv, dense_KT_mv,
                max_iters=max_iters, tol_primal=tol_primal, tol_gap=tol_gap)
    # report objective/residuals in ORIGINAL space
    x, y = unscale_solution(res.x, res.y, d_r, d_c)
    pr, gap, p_obj, d_obj = _kkt(jax.tree.map(lambda a: a[None], op),
                                 matvec_engine(), x[None], y[None])
    squeeze = lambda a: a[0]
    return SolveResult(x=x, y=y, primal_obj=squeeze(p_obj),
                       dual_obj=squeeze(d_obj), primal_res=squeeze(pr),
                       gap=squeeze(gap),
                       iterations=res.iterations, converged=res.converged,
                       n_restarts=res.n_restarts, diverged=res.diverged)


def solve_batched(op_batched: OperatorLP, K_mv=dense_K_mv, KT_mv=dense_KT_mv,
                  **kw) -> SolveResult:
    """vmap over the leading (sub-problem) axis — POP's map step on one
    device.  ``core/backends.py`` wraps this in shard_map for the mesh path
    and swaps in the fused engines for dense/structured problems."""
    return jax.vmap(lambda o: solve(o, K_mv, KT_mv, **kw))(op_batched)
