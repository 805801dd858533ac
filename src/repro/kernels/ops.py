"""Public jit'd wrappers around the Pallas kernels.

Handles (a) padding arbitrary shapes up to block multiples and slicing
results back, and (b) platform dispatch.  Every wrapper takes a
``backend`` keyword:

``None`` / ``"auto"``
    The fast path for the platform.  Dense kernels: compiled Pallas on
    TPU, the pure-jnp reference (``ref.py`` — algebraically identical,
    XLA-fused) everywhere else.  Gather kernels (the structured and
    streaming-full families): the ``ref.py`` form on EVERY platform — the
    TPU's Pallas compiler (Mosaic) lowers a gather only inside one
    128-lane vreg, and these kernels gather from whole lane vectors
    thousands of columns long (see ``README.md``, "What runs on a TPU").
    Interpret-mode Pallas is ~1000x too slow for a PDHG inner loop, so it
    is never chosen implicitly, and no mode is ever retried in another.
``"pallas"``
    Force compiled Pallas (fails off-TPU, and for the gather families on
    the TPU too — debugging aid).
``"interpret"``
    Force the Pallas interpreter (runs anywhere; exercises the real kernel
    bodies + padding logic on CPU — what ``tests/test_kernels.py`` and the
    step-engine padding tests use).
``"xla"``
    Force the pure-jnp reference (A/B benchmarking escape hatch).

Two kernel families back the step engines in ``core/pdhg.py``:

* dense (``fused_forward_step`` / ``fused_backward_step`` +
  ``bmatvec``/``bmatvec_t``) — blocked matmul kernels over an explicit
  [k, M, N] constraint matrix (``kernels/fused_pdhg_step.py``);
* structured (``structured_forward_step`` / ``structured_backward_step`` +
  ``smatvec``/``smatvec_t``) — gather/segment-reduce kernels over ELL
  index metadata (``core/pdhg.StructuredOperator``,
  ``kernels/structured_pdhg_step.py``).  Off-TPU the reference path is
  pure ``take_along_axis`` gathers — no scatter, unlike the
  ``segment_sum`` scatter-adds in typical domain matvecs.

A third family streams the **single-lane full problem**
(``structured_full_forward_step`` / ``structured_full_backward_step`` +
``smatvec_full``/``smatvec_t_full``): M-blocked phased-grid kernels whose
VMEM residency is bounded by the ``FULL_BLOCK_*`` tile sizes, with the
wide-bucket add-back as a fold-map gather and optional int8/bf16
coefficient storage dequantized in-kernel.  The ``plan`` keyword is the
static ragged wide-block plan from ``core/pdhg._wide_block_plan`` — the
XLA reference applies it in full; the Pallas path uses it to trim the
streamed wide width to the plan maximum.

A solver constructed once picks the right kernel per platform at trace
time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import pdhg_matvec as _mv
from . import fused_pdhg_step as _fused
from . import structured_pdhg_step as _structured
from . import ref as _ref

_MODES = (None, "auto", "pallas", "interpret", "xla")

# lane-axis multiple for the structured kernels' full-lane blocks
STRUCT_ALIGN = 128


def _resolve_mode(backend: str | None, gather: bool = False) -> str:
    """'pallas' | 'interpret' | 'xla' from a user-facing backend name;
    ``gather`` marks the ELL gather families, whose fast path is the XLA
    form on every platform."""
    if backend not in _MODES:
        raise ValueError(f"unknown kernel backend {backend!r}; "
                         f"expected one of {_MODES}")
    if backend in (None, "auto"):
        if gather:
            return "xla"
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend


def kernel_mode(family: str, backend: str | None = None) -> str:
    """What a step engine's kernels run as here — ``"pallas"``,
    ``"interpret"`` or ``"xla"`` — for a kernel ``family`` (``"dense"``,
    ``"gather"``, or ``"matvec"`` for the vmapped jnp engine, always
    ``"xla"``).  Engines record it so every result names what ran."""
    if family == "matvec":
        return "xla"
    if family not in ("dense", "gather"):
        raise ValueError(f"unknown kernel family {family!r}")
    return _resolve_mode(backend, gather=family == "gather")


def _pad_to(a: jnp.ndarray, axis: int, mult: int, value: float = 0.0):
    size = a.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def bmatvec(A, x, *, backend: str | None = None,
            block_m: int = _mv.BLOCK_M, block_n: int = _mv.BLOCK_N):
    """y = A @ x batched over leading axis; any [k, M, N] shape."""
    mode = _resolve_mode(backend)
    if mode == "xla":
        return _ref.bmatvec(A, x)
    k, M, N = A.shape
    Ap = _pad_to(_pad_to(A, 1, block_m), 2, block_n)
    xp = _pad_to(x, 1, block_n)
    y = _mv.bmatvec(Ap, xp, block_m=block_m, block_n=block_n,
                    interpret=mode == "interpret")
    return y[:, :M]


def bmatvec_t(A, y, *, backend: str | None = None,
              block_m: int = _mv.BLOCK_M, block_n: int = _mv.BLOCK_N):
    """x = A^T @ y batched over leading axis."""
    mode = _resolve_mode(backend)
    if mode == "xla":
        return _ref.bmatvec_t(A, y)
    k, M, N = A.shape
    Ap = _pad_to(_pad_to(A, 1, block_m), 2, block_n)
    yp = _pad_to(y, 1, block_m)
    x = _mv.bmatvec_t(Ap, yp, block_m=block_m, block_n=block_n,
                      interpret=mode == "interpret")
    return x[:, :N]


def fused_forward_step(A, x, c, l, u, tau, kty, *, backend: str | None = None,
                       block_m: int = _mv.BLOCK_M, block_n: int = _mv.BLOCK_N):
    """(x_new, kx) — fused clip(x - tau(c + kty)) + forward matvec.

    Padded variables get l = u = 0 blocks (pinned to zero, matching the
    LinearProgram padding contract), so the sliced-back result equals the
    unpadded math exactly."""
    mode = _resolve_mode(backend)
    if mode == "xla":
        return _ref.fused_forward_step(A, x, c, l, u, tau[:, None], kty)
    k, M, N = A.shape
    Ap = _pad_to(_pad_to(A, 1, block_m), 2, block_n)
    pad_vec = lambda v: _pad_to(v, 1, block_n)
    xn, kx = _fused.fused_forward_step(
        Ap, pad_vec(x), pad_vec(c), pad_vec(l), pad_vec(u), tau, pad_vec(kty),
        block_m=block_m, block_n=block_n, interpret=mode == "interpret")
    return xn[:, :N], kx[:, :M]


def fused_backward_step(A, y, q, sigma, ineq_mask, kx_new, kx_prev, *,
                        backend: str | None = None,
                        block_m: int = _mv.BLOCK_M, block_n: int = _mv.BLOCK_N):
    """(y_new, kty) — fused proj(y + sigma(2*kx_new - kx_prev - q)) +
    adjoint matvec."""
    mode = _resolve_mode(backend)
    if mode == "xla":
        return _ref.fused_backward_step(A, y, q, sigma[:, None], ineq_mask,
                                        kx_new, kx_prev)
    k, M, N = A.shape
    Ap = _pad_to(_pad_to(A, 1, block_m), 2, block_n)
    pad_vec = lambda v: _pad_to(v, 1, block_m)
    yn, kty = _fused.fused_backward_step(
        Ap, pad_vec(y), pad_vec(q), sigma, pad_vec(ineq_mask),
        pad_vec(kx_new), pad_vec(kx_prev),
        block_m=block_m, block_n=block_n, interpret=mode == "interpret")
    return yn[:, :M], kty[:, :N]


# --------------------------------------------------------------------------
# structured (ELL gather/segment-reduce) family — ``s`` is a
# ``core/pdhg.StructuredOperator`` with batched [k, W, {M|N}] leaves
# --------------------------------------------------------------------------

def smatvec(s, x, *, plan=()):
    """kx = K x through the row-side gather layout.  Pure gather-reduce —
    the XLA form is the fast path on every platform; ``plan`` is a static
    ragged wide-block plan (``core/pdhg._wide_block_plan``)."""
    return _ref.smatvec(s, x, plan)


def smatvec_t(s, y, *, plan=()):
    """kty = K^T y through the column-side gather layout."""
    return _ref.smatvec_t(s, y, plan)


def _pad_row_side(s):
    """Pad the row-side leaves' lane axes to STRUCT_ALIGN multiples for
    the full-lane Pallas blocks (padded bucket columns feed row 0 with
    val 0 — exact no-ops)."""
    return s._replace(
        row_idx=_pad_to(s.row_idx, 2, STRUCT_ALIGN),
        row_val=_pad_to(s.row_val, 2, STRUCT_ALIGN),
        wrow_idx=_pad_to(s.wrow_idx, 2, STRUCT_ALIGN),
        wrow_val=_pad_to(s.wrow_val, 2, STRUCT_ALIGN),
        wrow_ids=_pad_to(s.wrow_ids, 1, STRUCT_ALIGN))


def _pad_col_side(s):
    return s._replace(
        col_idx=_pad_to(s.col_idx, 2, STRUCT_ALIGN),
        col_val=_pad_to(s.col_val, 2, STRUCT_ALIGN),
        wcol_idx=_pad_to(s.wcol_idx, 2, STRUCT_ALIGN),
        wcol_val=_pad_to(s.wcol_val, 2, STRUCT_ALIGN),
        wcol_ids=_pad_to(s.wcol_ids, 1, STRUCT_ALIGN))


def structured_forward_step(s, x, c, l, u, tau, kty, *, plan=(),
                            backend: str | None = None):
    """(x_new, kx) for a structured operator: one gather/segment-reduce
    launch for the whole k-stack (the XLA reference unless the Pallas
    kernel is forced)."""
    mode = _resolve_mode(backend, gather=True)
    if mode == "xla":
        return _ref.structured_forward_step(s, x, c, l, u, tau[:, None], kty,
                                            plan)
    M = s.row_idx.shape[-1]
    N = x.shape[1]
    pad_vec = lambda v: _pad_to(v, 1, STRUCT_ALIGN)
    xn, kx = _structured.structured_forward_step(
        _pad_row_side(s), pad_vec(x), pad_vec(c), pad_vec(l), pad_vec(u),
        tau, pad_vec(kty), interpret=mode == "interpret")
    return xn[:, :N], kx[:, :M]


def structured_backward_step(s, y, q, sigma, ineq_mask, kx_new, kx_prev, *,
                             plan=(), backend: str | None = None):
    """(y_new, kty) for a structured operator."""
    mode = _resolve_mode(backend, gather=True)
    if mode == "xla":
        return _ref.structured_backward_step(s, y, q, sigma[:, None],
                                             ineq_mask, kx_new, kx_prev, plan)
    N = s.col_idx.shape[-1]
    M = y.shape[1]
    pad_vec = lambda v: _pad_to(v, 1, STRUCT_ALIGN)
    yn, kty = _structured.structured_backward_step(
        _pad_col_side(s), pad_vec(y), pad_vec(q), pad_vec(ineq_mask),
        pad_vec(kx_new), pad_vec(kx_prev), sigma,
        interpret=mode == "interpret")
    return yn[:, :M], kty[:, :N]


# --------------------------------------------------------------------------
# streaming full-problem (single-lane, M-blocked) family
# --------------------------------------------------------------------------

def smatvec_full(s, x, *, plan=()):
    """kx = K x for the single-lane full problem: fold-map wide add-back
    + ragged wide-block plan.  Like ``smatvec``, the XLA form is the fast
    path for the out-of-loop uses on every platform."""
    return _ref.smatvec_full(s, x, plan)


def smatvec_t_full(s, y, *, plan=()):
    """kty = K^T y through the column-side full layout."""
    return _ref.smatvec_t_full(s, y, plan)


def _sublane(dtype) -> int:
    """Sublane multiple for a coefficient dtype (8 f32 / 16 bf16 /
    32 int8 — second-minor tiling is 32 bytes)."""
    return 32 // jnp.dtype(dtype).itemsize


def _pad_full_side(idx, val, scale, widx, wval, wscale, fold, plan,
                   block_m, block_w, block_d):
    """Pad one gather side for the streaming kernels: narrow [1, W, S]
    to (sublane-mult, block-mult) tiles, wide [1, Ww, D] trimmed to the
    plan's max effective width then tiled, D padded PAST the bucket end
    so the fold map's zero slot lands in an all-padding (exact-zero)
    column.  Block sizes shrink to the padded extent on small problems
    so the grid never over-runs the data."""
    sub = _sublane(val.dtype)
    idx = _pad_to(idx, 1, sub)
    val = _pad_to(val, 1, sub)
    bm = min(block_m, -(-idx.shape[2] // STRUCT_ALIGN) * STRUCT_ALIGN)
    idx = _pad_to(idx, 2, bm)
    val = _pad_to(val, 2, bm)
    fold = _pad_to(fold, 1, bm)
    if plan:
        weff = min(widx.shape[1], max(wb for _, _, wb in plan))
        widx = widx[:, :weff, :]
        wval = wval[:, :weff, :]
    bw = min(block_w, -(-widx.shape[1] // sub) * sub)
    widx = _pad_to(widx, 1, bw)
    wval = _pad_to(wval, 1, bw)
    d = widx.shape[2]
    bd = min(block_d, -(-(d + 1) // STRUCT_ALIGN) * STRUCT_ALIGN)
    widx = _pad_to(jnp.pad(widx, ((0, 0), (0, 0), (0, 1))), 2, bd)
    wval = _pad_to(jnp.pad(wval, ((0, 0), (0, 0), (0, 1))), 2, bd)
    ones = jnp.ones((1, 1), jnp.float32)
    mk_scale = lambda sc: ones if sc is None else sc.reshape(1, 1)
    return (idx, val, mk_scale(scale), widx, wval, mk_scale(wscale),
            fold, bm, bw, bd)


def structured_full_forward_step(s, x, c, l, u, tau, kty, *, plan=(),
                                 backend: str | None = None,
                                 block_m: int = _structured.FULL_BLOCK_M,
                                 block_w: int = _structured.FULL_BLOCK_W,
                                 block_d: int = _structured.FULL_BLOCK_D):
    """(x_new, kx) for the single-lane full problem: one M-blocked
    streaming launch (the ragged-plan XLA reference unless the Pallas
    kernel is forced)."""
    mode = _resolve_mode(backend, gather=True)
    if mode == "xla" or s.row_idx.shape[0] != 1:
        return _ref.structured_full_forward_step(s, x, c, l, u,
                                                 tau[:, None], kty, plan)
    M = s.row_idx.shape[-1]
    N = x.shape[1]
    ri, rv, rs, wri, wrv, wrs, fold, bm, bw, bd = _pad_full_side(
        s.row_idx, s.row_val, s.row_scale, s.wrow_idx, s.wrow_val,
        s.wrow_scale, s.row_fold, plan, block_m, block_w, block_d)
    pad_vec = lambda v: _pad_to(v, 1, STRUCT_ALIGN)
    xn, kx = _structured.structured_full_forward_step(
        ri, rv, rs, wri, wrv, wrs, fold,
        pad_vec(x), pad_vec(c), pad_vec(l), pad_vec(u), pad_vec(kty),
        tau[:, None], block_m=bm, block_w=bw, block_d=bd,
        interpret=mode == "interpret")
    return xn[:, :N], kx[:, :M]


def structured_full_backward_step(s, y, q, sigma, ineq_mask, kx_new,
                                  kx_prev, *, plan=(),
                                  backend: str | None = None,
                                  block_m: int = _structured.FULL_BLOCK_M,
                                  block_w: int = _structured.FULL_BLOCK_W,
                                  block_d: int = _structured.FULL_BLOCK_D):
    """(y_new, kty) for the single-lane full problem (column side)."""
    mode = _resolve_mode(backend, gather=True)
    if mode == "xla" or s.col_idx.shape[0] != 1:
        return _ref.structured_full_backward_step(
            s, y, q, sigma[:, None], ineq_mask, kx_new, kx_prev, plan)
    N = s.col_idx.shape[-1]
    M = y.shape[1]
    ci, cv, cs, wci, wcv, wcs, fold, bm, bw, bd = _pad_full_side(
        s.col_idx, s.col_val, s.col_scale, s.wcol_idx, s.wcol_val,
        s.wcol_scale, s.col_fold, plan, block_m, block_w, block_d)
    pad_vec = lambda v: _pad_to(v, 1, STRUCT_ALIGN)
    yn, kty = _structured.structured_full_backward_step(
        ci, cv, cs, wci, wcv, wcs, fold,
        pad_vec(y), pad_vec(q), pad_vec(ineq_mask), pad_vec(kx_new),
        pad_vec(kx_prev), sigma[:, None], block_m=bm, block_w=bw,
        block_d=bd, interpret=mode == "interpret")
    return yn[:, :M], kty[:, :N]
