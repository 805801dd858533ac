"""Pallas kernels: FUSED PDHG half-steps for STRUCTURED operators.

Not compiled on a TPU: Mosaic, the TPU's Pallas compiler, lowers a gather
only inside one 128-lane vreg, and every kernel here gathers from whole
lane vectors thousands of columns long.  ``kernels/ops.py`` therefore
dispatches these half-steps to their XLA forms (``ref.py``) on every
platform; the kernels run in interpret mode, where the tests check them
against those forms (see ``README.md``, "What runs on a TPU").

The structured LPs (Gavel per-job rows, traffic per-commodity path sums,
load-balancing server groups) apply K through segment-sums and gathers, not
dense matmuls.  In the two-bucket ELL index form
(``core/pdhg.StructuredOperator``) both matvec directions become *gather +
multiply + reduce over the nnz axis* — no scatter anywhere, because the
transpose layout is precomputed at build time and the few wide segments
(worker-cap rows, hot edges, per-server load rows) live in their own
compact bucket whose results are folded back with a one-hot accumulation.
These kernels run one half-step for the WHOLE stacked k-lane batch per
launch, with the element-wise tail (axpy + projection) fused in front of
the gathers so the updated iterate never round-trips HBM between the tail
and the matvec that consumes it:

  structured_forward_step :
      x_new = clip(x - tau*(c + kty), l, u)           (kty = carried K^T y)
      kx    = narrow_rows(x_new) + onehot(wrow_ids) . wide_rows(x_new)
  structured_backward_step:
      y_new = proj_{>=0 on ineq}(y + sigma*(2*kx - kx_prev - q))
      kty   = narrow_cols(y_new) + onehot(wcol_ids) . wide_cols(y_new)

Grid is ``(k,)``: each program owns one lane, whose vectors live entirely
in VMEM (POP sub-problems are small by construction — the k^2 variable
reduction is the paper's point — so a lane's [N] + [W, M] blocks fit
comfortably).  The nnz axis rides the sublanes (arrays are [W, M]
nnz-major) so the reduce is a sublane reduction and rows/cols stay on the
128-wide lane axis.  The lane axis is squeezed out of every block, so the
last two block dims are an array's own (the TPU's block-shape rule), and
the per-lane step sizes (tau, sigma) are one ``[k]`` array in SMEM.  A
lane of a k=64 paper-scale traffic stack outgrows the default scoped
VMEM, so the launch sets its VMEM limit from the lane's size.

The FULL unpartitioned problem at paper scale does NOT fit a lane in
VMEM; it takes the **M-blocked streaming family** below
(``structured_full_forward_step`` / ``structured_full_backward_step``):
a phased 1-D grid ``(1 + num_wide_blocks + num_m_blocks,)`` per
half-step —

  phase 0                  element-wise tail into a pinned full-vector
                           output block (readable by later phases);
  wide phases              stream ``(FULL_BLOCK_W, FULL_BLOCK_D)`` tiles
                           of the wide bucket, accumulating partial
                           reduces into a pinned ``[1, D]`` accumulator
                           output (flushed only once, at the end);
  narrow phases            stream ``(W, FULL_BLOCK_M)`` tiles of the
                           narrow ELL, each emitting one output block =
                           narrow reduce + ``accum[fold]`` — the
                           wide-bucket add-back is a gather through the
                           fold map, not a one-hot einsum.

Coefficient tiles may be int8/bf16 (``core/pdhg.quantize_structured``);
they are dequantized in-register (``* scale``) and accumulated in f32.
Each tile is <= FULL_BLOCK_W x FULL_BLOCK_M x 4 B, so VMEM stays bounded
regardless of problem size; off-TPU the dispatch in ``kernels/ops.py``
takes the XLA reference (``ref.smatvec_full``), which additionally
applies the fully ragged wide-block plan.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gather_side(idx, val, widx, wval, wids, v, n_out):
    """One matvec direction from VMEM-resident blocks: narrow ELL
    gather-reduce + wide-bucket gather-reduce folded in via one-hot
    (bucket ids are distinct; padded bucket columns feed id 0 with 0.0)."""
    out = jnp.sum(val * jnp.take(v, idx, axis=0), axis=0)       # [n_out]
    wide = jnp.sum(wval * jnp.take(v, widx, axis=0), axis=0)    # [D]
    onehot = (wids[:, None]
              == jax.lax.broadcasted_iota(jnp.int32, (wids.shape[0], n_out),
                                          1))
    return out + jnp.sum(wide[:, None] * onehot.astype(wide.dtype), axis=0)


def _forward_kernel(ri_ref, rv_ref, wri_ref, wrv_ref, wrids_ref,
                    x_ref, c_ref, l_ref, u_ref, kty_ref, tau_ref,
                    xn_ref, kx_ref):
    """grid = (k,): one lane per program, everything VMEM-resident."""
    tau = tau_ref[pl.program_id(0)]
    x_new = jnp.clip(x_ref[0] - tau * (c_ref[0] + kty_ref[0]),
                     l_ref[0], u_ref[0])
    xn_ref[0, :] = x_new.astype(xn_ref.dtype)
    kx = _gather_side(ri_ref[...], rv_ref[...], wri_ref[...], wrv_ref[...],
                      wrids_ref[0], x_new, kx_ref.shape[-1])
    kx_ref[0, :] = kx.astype(kx_ref.dtype)


def _backward_kernel(ci_ref, cv_ref, wci_ref, wcv_ref, wcids_ref,
                     y_ref, q_ref, mask_ref, kxn_ref, kxp_ref, sig_ref,
                     yn_ref, kty_ref):
    """grid = (k,): dual tail + adjoint gather-reduce."""
    sigma = sig_ref[pl.program_id(0)]
    y_new = y_ref[0] + sigma * (2.0 * kxn_ref[0] - kxp_ref[0] - q_ref[0])
    y_new = jnp.where(mask_ref[0] != 0, jnp.maximum(y_new, 0.0), y_new)
    yn_ref[0, :] = y_new.astype(yn_ref.dtype)
    kty = _gather_side(ci_ref[...], cv_ref[...], wci_ref[...], wcv_ref[...],
                       wcids_ref[0], y_new, kty_ref.shape[-1])
    kty_ref[0, :] = kty.astype(kty_ref.dtype)


def _lane(shape):
    """BlockSpec for one lane of a ``[k, ...]`` array: the lane axis is
    squeezed, so the last two block dims are the array's own (the TPU's
    block-shape rule refuses a ``(1, n)`` block over ``[k, n]``)."""
    return pl.BlockSpec((None,) + tuple(shape),
                        lambda i: (i,) + (0,) * len(shape))


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _lane_vmem_limit(arrays) -> int:
    """Scoped-VMEM budget for a whole-lane launch: every operand and output
    holds one lane, double-buffered, plus headroom for the gathered
    temporaries.  A lane of a k=64 paper-scale traffic stack (~31k
    columns) outgrows the 16 MiB default, so the limit follows the lane
    size instead, capped below the chip's 128 MiB."""
    per_lane = sum(a.size // a.shape[0] * a.dtype.itemsize for a in arrays)
    return min(max(6 * per_lane, 16 << 20), 100 << 20)


def _lane_call(kernel, s_side, vectors, step, n_out, interpret):
    """Launch a whole-lane half-step: ``s_side`` = the five ELL leaves of
    one side ([k, W, S] / [k, Ww, D] / [k, D]), ``vectors`` the [k, n]
    tail operands, ``step`` the [k] step sizes; returns the updated
    iterate [k, n] and the matvec product [k, n_out]."""
    idx, val, widx, wval, wids = s_side
    k, n = vectors[0].shape
    row = lambda v: v[:, None, :]
    ins = [idx, val, widx, wval, row(wids)] + [
        row(v.astype(jnp.int32) if v.dtype == jnp.bool_ else v)
        for v in vectors]
    out = [jax.ShapeDtypeStruct((k, 1, n), jnp.float32),
           jax.ShapeDtypeStruct((k, 1, n_out), jnp.float32)]
    new, prod = pl.pallas_call(
        kernel,
        grid=(k,),
        in_specs=[_lane(a.shape[1:]) for a in ins] + [_SMEM],
        out_specs=[_lane((1, n)), _lane((1, n_out))],
        out_shape=out,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_lane_vmem_limit(ins + out)),
        interpret=interpret,
    )(*ins, step.astype(jnp.float32))
    return new[:, 0], prod[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def structured_forward_step(s, x, c, l, u, tau, kty, *,
                            interpret: bool = False):
    """Returns (x_new, kx).  ``s`` is a batched StructuredOperator
    (row-side leaves [k, Wr, M] / [k, Ww, Dr] / [k, Dr]); x/c/l/u/kty:
    [k, N]; tau: [k] (per-sub-problem step size — POP sub-problems restart
    independently, so step sizes diverge across the batch)."""
    return _lane_call(
        _forward_kernel,
        (s.row_idx, s.row_val, s.wrow_idx, s.wrow_val, s.wrow_ids),
        (x, c, l, u, kty), tau, s.row_idx.shape[-1], interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def structured_backward_step(s, y, q, ineq_mask, kx_new, kx_prev, sigma, *,
                             interpret: bool = False):
    """Returns (y_new, kty).  ``s`` carries the column-side leaves
    ([k, Wc, N] / [k, Wv, Dc] / [k, Dc]); y/q/ineq_mask/kx_new/kx_prev:
    [k, M]; sigma: [k]."""
    return _lane_call(
        _backward_kernel,
        (s.col_idx, s.col_val, s.wcol_idx, s.wcol_val, s.wcol_ids),
        (y, q, ineq_mask, kx_new, kx_prev), sigma, s.col_idx.shape[-1],
        interpret)
# --------------------------------------------------------------------------
# M-blocked streaming family: the single-lane FULL problem
# --------------------------------------------------------------------------

# per-tile block sizes for the streaming full kernels; every VMEM-resident
# tile is bounded by these regardless of problem size (popcheck's
# pallas-vmem-budget rule resolves them through the keyword defaults below)
FULL_BLOCK_M = 512   # output-segment lane-axis tile (kx rows / kty cols)
FULL_BLOCK_W = 512   # wide-bucket nnz (sublane) tile
FULL_BLOCK_D = 512   # wide-bucket column (lane) tile


def _full_forward_kernel(ri_ref, rv_ref, rs_ref, wri_ref, wrv_ref, wrs_ref,
                         fold_ref, x_ref, c_ref, l_ref, u_ref, kty_ref,
                         tau_ref, xn_ref, ws_ref, kx_ref, *,
                         nwv: int, nww: int):
    """Phased grid (1 + nwv + nm,): tail, then nwv wide tiles
    (nww sublane-tiles per column-tile), then the M-blocked narrow
    phases.  ``xn`` and ``ws`` are pinned outputs that double as
    cross-phase VMEM state (their block index never changes, so they are
    flushed exactly once)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _tail():
        tau = tau_ref[0, 0]
        xn_ref[0, :] = jnp.clip(
            x_ref[0] - tau * (c_ref[0] + kty_ref[0]), l_ref[0], u_ref[0])

    @pl.when((i >= 1) & (i < 1 + nwv))
    def _wide():
        p = i - 1
        wb = p % nww
        db = p // nww
        wv = wrv_ref[0].astype(jnp.float32) * wrs_ref[0, 0]
        part = jnp.sum(wv * jnp.take(xn_ref[0], wri_ref[0], axis=0), axis=0)
        bd = part.shape[0]
        sl = pl.ds(db * bd, bd)
        prev = jnp.where(wb == 0, jnp.zeros_like(part), ws_ref[0, sl])
        ws_ref[0, sl] = prev + part

    @pl.when(i >= 1 + nwv)
    def _narrow():
        rv = rv_ref[0].astype(jnp.float32) * rs_ref[0, 0]
        out = jnp.sum(rv * jnp.take(xn_ref[0], ri_ref[0], axis=0), axis=0)
        kx_ref[0, :] = out + jnp.take(ws_ref[0], fold_ref[0], axis=0)


def _full_backward_kernel(ci_ref, cv_ref, cs_ref, wci_ref, wcv_ref, wcs_ref,
                          fold_ref, y_ref, q_ref, mask_ref, kxn_ref, kxp_ref,
                          sig_ref, yn_ref, ws_ref, kty_ref, *,
                          nwv: int, nww: int):
    """Backward mirror: dual tail, column-side wide tiles, N-blocked
    narrow phases."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _tail():
        sigma = sig_ref[0, 0]
        y_new = y_ref[0] + sigma * (2.0 * kxn_ref[0] - kxp_ref[0] - q_ref[0])
        yn_ref[0, :] = jnp.where(mask_ref[0], jnp.maximum(y_new, 0.0), y_new)

    @pl.when((i >= 1) & (i < 1 + nwv))
    def _wide():
        p = i - 1
        wb = p % nww
        db = p // nww
        wv = wcv_ref[0].astype(jnp.float32) * wcs_ref[0, 0]
        part = jnp.sum(wv * jnp.take(yn_ref[0], wci_ref[0], axis=0), axis=0)
        bd = part.shape[0]
        sl = pl.ds(db * bd, bd)
        prev = jnp.where(wb == 0, jnp.zeros_like(part), ws_ref[0, sl])
        ws_ref[0, sl] = prev + part

    @pl.when(i >= 1 + nwv)
    def _narrow():
        cv = cv_ref[0].astype(jnp.float32) * cs_ref[0, 0]
        out = jnp.sum(cv * jnp.take(yn_ref[0], ci_ref[0], axis=0), axis=0)
        kty_ref[0, :] = out + jnp.take(ws_ref[0], fold_ref[0], axis=0)


def _pin(b):
    """BlockSpec for a block pinned at the origin for every phase."""
    return pl.BlockSpec(b, lambda i: (0,) * len(b))


def _full_call(kernel, narrow, wide, fold, vectors, scalars,
               bm=FULL_BLOCK_M, bw=FULL_BLOCK_W, bd=FULL_BLOCK_D,
               interpret=False):
    """Shared launcher for the streaming full kernels.

    ``narrow`` = (idx, val, scale) [1, W, S]-shaped (S = blocked output
    segments), ``wide`` = (widx, wval, wscale) [1, Ww, D]-shaped,
    ``vectors`` = the [1, V] tail operands, ``scalars`` = the (1, 1)
    step-size blocks.  Grid = (1 + nwv + nm,) with all index maps
    clip-pinned so a block only moves (and is only re-copied / flushed)
    in the phases that use it."""
    _, wr, s_pad = narrow[0].shape
    _, ww, d_pad = wide[0].shape
    nv_shape = vectors[0].shape[1]
    nm = s_pad // bm
    nww = ww // bw
    nd = d_pad // bd
    nwv = nww * nd

    def wide_map(i):
        p = jnp.clip(i - 1, 0, nwv - 1)
        return (0, p % nww, p // nww)

    def narrow_map3(i):
        return (0, 0, jnp.clip(i - 1 - nwv, 0, nm - 1))

    def narrow_map2(i):
        return (0, jnp.clip(i - 1 - nwv, 0, nm - 1))

    out = [jax.ShapeDtypeStruct((1, nv_shape), jnp.float32),
           jax.ShapeDtypeStruct((1, d_pad), jnp.float32),
           jax.ShapeDtypeStruct((1, s_pad), jnp.float32)]
    res = pl.pallas_call(
        functools.partial(kernel, nwv=nwv, nww=nww),
        grid=(1 + nwv + nm,),
        in_specs=[
            pl.BlockSpec((1, wr, bm), narrow_map3),
            pl.BlockSpec((1, wr, bm), narrow_map3),
            _pin((1, 1)),
            pl.BlockSpec((1, bw, bd), wide_map),
            pl.BlockSpec((1, bw, bd), wide_map),
            _pin((1, 1)),
            pl.BlockSpec((1, bm), narrow_map2),
        ] + [_pin((1, nv_shape))] * len(vectors)
          + [_pin((1, 1))] * len(scalars),
        out_specs=[_pin((1, nv_shape)), _pin((1, d_pad)),
                   pl.BlockSpec((1, bm), narrow_map2)],
        out_shape=out,
        interpret=interpret,
    )(*narrow, *wide, fold, *vectors, *scalars)
    xn, _, kx = res
    return xn, kx


@functools.partial(jax.jit, static_argnames=("block_m", "block_w",
                                             "block_d", "interpret"))
def structured_full_forward_step(ri, rv, rs, wri, wrv, wrs, fold,
                                 x, c, l, u, kty, tau, *,
                                 block_m: int = FULL_BLOCK_M,
                                 block_w: int = FULL_BLOCK_W,
                                 block_d: int = FULL_BLOCK_D,
                                 interpret: bool = False):
    """Streaming full forward half-step.  Returns (x_new, kx).

    Row-side inputs are pre-padded by ``kernels/ops.py``: ``ri/rv``
    [1, Wr, M_pad] with M_pad a ``block_m`` multiple, ``wri/wrv``
    [1, Ww_pad, D_pad] with Ww_pad / D_pad multiples of
    ``block_w`` / ``block_d`` and D_pad > D (the fold map's zero slot
    lands in an all-padding column), ``fold`` [1, M_pad], vectors
    [1, N_pad], scales / tau (1, 1).  ``rv``/``wrv`` may be f32, bf16 or
    int8 — dequantized in-register against the scale blocks."""
    return _full_call(_full_forward_kernel, (ri, rv, rs), (wri, wrv, wrs),
                      fold, (x, c, l, u, kty), (tau,),
                      block_m, block_w, block_d, interpret)


@functools.partial(jax.jit, static_argnames=("block_m", "block_w",
                                             "block_d", "interpret"))
def structured_full_backward_step(ci, cv, cs, wci, wcv, wcs, fold,
                                  y, q, ineq_mask, kx_new, kx_prev, sigma, *,
                                  block_m: int = FULL_BLOCK_M,
                                  block_w: int = FULL_BLOCK_W,
                                  block_d: int = FULL_BLOCK_D,
                                  interpret: bool = False):
    """Streaming full backward half-step (column side; ``block_m`` tiles
    the N output segments).  Returns (y_new, kty)."""
    return _full_call(_full_backward_kernel, (ci, cv, cs), (wci, wcv, wcs),
                      fold, (y, q, ineq_mask, kx_new, kx_prev), (sigma,),
                      block_m, block_w, block_d, interpret)
