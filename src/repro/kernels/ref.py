"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth; ``tests/test_kernels.py`` sweeps
shapes/dtypes and asserts the Pallas implementations (interpret mode on CPU,
compiled on TPU) match these to tolerance.  Off-TPU these ARE the dispatch
targets (``ops.py``), so they are written to be XLA-friendly: the
structured paths use ``take_along_axis`` gathers and axis reductions — no
scatters anywhere.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def bmatvec(A: jnp.ndarray, x: jnp.ndarray) -> jnp.ndarray:
    """y[k, m] = sum_n A[k, m, n] * x[k, n]   (f32 accumulation)."""
    return jnp.einsum("kmn,kn->km", A, x, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def bmatvec_t(A: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
    """x[k, n] = sum_m A[k, m, n] * y[k, m]   (A read transposed)."""
    return jnp.einsum("kmn,km->kn", A, y, preferred_element_type=jnp.float32,
                      precision=jax.lax.Precision.HIGHEST)


def _bgather(v: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """v[k, n] gathered per lane by idx [k, ...] -> [k, ...]."""
    k = idx.shape[0]
    return jnp.take_along_axis(v, idx.reshape(k, -1), axis=1).reshape(idx.shape)


def _gather_side(idx, val, widx, wval, wids, v, n_out):
    """One direction of the two-bucket ELL matvec (``K x`` through the row
    side, ``K^T y`` through the column side) for an operator without fold
    maps:

        out = sum_w val[:, w, :] * v[idx[:, w, :]]              (narrow)
        out += scatter(wids, sum_w wval[:, w, :] * v[widx[:, w, :]])

    All gathers; the wide-bucket results land via a one-hot accumulation
    (bucket ids are distinct, so order never matters), contracted at full
    f32 precision.  Padding entries (idx 0, val 0) and empty buckets
    contribute exact zeros.
    """
    out = jnp.sum(val * _bgather(v, idx), axis=-2)           # [k, n_out]
    wide = jnp.sum(wval * _bgather(v, widx), axis=-2)        # [k, D]
    onehot = (wids[:, :, None] == jnp.arange(n_out)[None, None, :])
    return out + jnp.einsum("kd,kdm->km", wide, onehot.astype(wide.dtype),
                            precision=jax.lax.Precision.HIGHEST)


def smatvec(s, x, plan=()):
    """kx[k, m] = (K x) through the row-side gather layout of a
    ``core/pdhg.StructuredOperator`` (padding entries carry val 0).  An
    operator with fold maps takes the fold-map add-back and the ragged
    ``plan`` of :func:`smatvec_full`."""
    if s.row_fold is not None:
        return smatvec_full(s, x, plan)
    return _gather_side(s.row_idx, s.row_val, s.wrow_idx, s.wrow_val,
                        s.wrow_ids, x, s.row_idx.shape[-1])


def smatvec_t(s, y, plan=()):
    """kty[k, n] = (K^T y) through the column-side gather layout."""
    if s.col_fold is not None:
        return smatvec_t_full(s, y, plan)
    return _gather_side(s.col_idx, s.col_val, s.wcol_idx, s.wcol_val,
                        s.wcol_ids, y, s.col_idx.shape[-1])


def fused_forward_step(A, x, c, l, u, tau, kty):
    """PDHG primal half-step + forward product:

        x_new = clip(x - tau * (c + kty), l, u)       (kty = carried K^T y)
        kx    = A @ x_new

    Returns (x_new, kx).  The Pallas version fuses the tail with the
    matvec so x_new feeds the product without an HBM round-trip.
    """
    x_new = jnp.clip(x - tau * (c + kty), l, u)
    return x_new, bmatvec(A, x_new)


def fused_backward_step(A, y, q, sigma, ineq_mask, kx_new, kx_prev):
    """PDHG dual half-step + adjoint product:

        y_new = y + sigma * (2*kx_new - kx_prev - q)   (K x_bar by linearity)
        y_new = max(y_new, 0) where ineq_mask          (inequality duals)
        kty   = A^T @ y_new

    Returns (y_new, kty).
    """
    y_new = y + sigma * (2.0 * kx_new - kx_prev - q)
    y_new = jnp.where(ineq_mask, jnp.maximum(y_new, 0.0), y_new)
    return y_new, bmatvec_t(A, y_new)


def structured_forward_step(s, x, c, l, u, tau, kty, plan=()):
    """Structured-operator forward half-step (ELL gather-reduce matvec)."""
    x_new = jnp.clip(x - tau * (c + kty), l, u)
    return x_new, smatvec(s, x_new, plan)


def structured_backward_step(s, y, q, sigma, ineq_mask, kx_new, kx_prev,
                             plan=()):
    """Structured-operator backward half-step."""
    y_new = y + sigma * (2.0 * kx_new - kx_prev - q)
    y_new = jnp.where(ineq_mask, jnp.maximum(y_new, 0.0), y_new)
    return y_new, smatvec_t(s, y_new, plan)


# --------------------------------------------------------------------------
# full-problem (single-lane, M-blocked) oracles — the fused_structured_full
# engine's semantics: fold-map wide add-back instead of the one-hot einsum,
# ragged wide-block plan over the descending-sorted bucket, and in-graph
# dequantization of int8/bf16 coefficient storage (f32 accumulation)
# --------------------------------------------------------------------------

def _deq(val, scale):
    """Coefficients to f32: cast, then fold in the per-bucket dequant
    scale when the payload is int8-quantized (scale [k, 1] or None)."""
    v = val.astype(jnp.float32)
    return v if scale is None else v * scale[..., None]


def _gather_wide_sorted(widx, wval, wscale, fold, v, plan):
    """Wide-bucket reduce + fold-map add-back:

        wide[d] = sum_w wval[:, w, d] * v[widx[:, w, d]]     per plan block
        out     = pad(wide, 1)[fold]                          (a gather)

    ``plan`` is the static ragged block plan ``((c0, c1, wb), ...)`` from
    ``pdhg._wide_block_plan``: bucket columns are sorted by descending
    width, so slicing block ``[c0, c1)`` at its own max width ``wb`` skips
    the padding a uniform-width reduce would burn.  The fold map sends
    narrow segments to the one-past-the-end zero slot, hence the pad.
    """
    if not plan:
        plan = ((0, wval.shape[-1], wval.shape[-2]),)
    parts = [
        jnp.sum(_deq(wval[:, :wb, c0:c1], wscale)
                * _bgather(v, widx[:, :wb, c0:c1]), axis=-2)
        for (c0, c1, wb) in plan]
    wide = jnp.concatenate(parts, axis=-1)            # [k, D]
    wide = jnp.pad(wide, ((0, 0), (0, 1)))            # zero slot at D
    return _bgather(wide, fold)


def smatvec_full(s, x, plan=()):
    """kx = K x for the single-lane full problem: narrow ELL reduce plus
    the fold-map wide add-back (no one-hot einsum — at paper scale the
    one-hot materialises ~n_segments * D elements per matvec)."""
    narrow = jnp.sum(_deq(s.row_val, s.row_scale)
                     * _bgather(x, s.row_idx), axis=-2)
    return narrow + _gather_wide_sorted(
        s.wrow_idx, s.wrow_val, s.wrow_scale, s.row_fold, x, plan)


def smatvec_t_full(s, y, plan=()):
    """kty = K^T y through the column-side layout (see smatvec_full)."""
    narrow = jnp.sum(_deq(s.col_val, s.col_scale)
                     * _bgather(y, s.col_idx), axis=-2)
    return narrow + _gather_wide_sorted(
        s.wcol_idx, s.wcol_val, s.wcol_scale, s.col_fold, y, plan)


def structured_full_forward_step(s, x, c, l, u, tau, kty, plan=()):
    """Full-problem forward half-step: element-wise tail fused in front
    of the blocked row-side matvec."""
    x_new = jnp.clip(x - tau * (c + kty), l, u)
    return x_new, smatvec_full(s, x_new, plan)


def structured_full_backward_step(s, y, q, sigma, ineq_mask, kx_new,
                                  kx_prev, plan=()):
    """Full-problem backward half-step (column side)."""
    y_new = y + sigma * (2.0 * kx_new - kx_prev - q)
    y_new = jnp.where(ineq_mask, jnp.maximum(y_new, 0.0), y_new)
    return y_new, smatvec_t_full(s, y_new, plan)
