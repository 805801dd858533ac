"""Pallas TPU kernels: FUSED PDHG half-steps for DENSE operators.

The unfused PDHG iteration writes two full-length intermediates to HBM per
step (the primal gradient and the pre-projection dual).  At PDHG's
arithmetic intensity (~2 flop/byte, far below the TPU v5e ridge of ~240)
every avoided HBM round-trip is pure wall-clock.

These kernels fuse each half-step's element-wise tail with the matvec that
FOLLOWS it, in the same launch, and emit the matvec product as a second
output — the product the in-loop KKT check in ``core/pdhg.solve_stacked``
consumes for free:

  fused_forward_step  : x_new = clip(x - tau*(c + kty), l, u);  kx = K x_new
  fused_backward_step : y_new = proj_{>=0 on ineq}(y + sigma*(2*kx - kx_prev - q))
                        kty   = K^T y_new

(``kty`` in the forward step is the CARRIED K^T y from the previous
backward step; the dual extrapolation uses 2*K x_new - K x_prev — linearity
of K — instead of a second matvec on the extrapolated point.)

Vectors travel as ``[k, 1, n]`` views with the lane axis squeezed out of
their blocks (see ``pdhg_matvec.py``), and the per-lane step sizes (tau,
sigma) as one ``[k]`` array in SMEM read at the lane's grid index.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pdhg_matvec import (BLOCK_M, BLOCK_N, lane_vec, row_times_tile,
                          row_times_tile_t)


def _fused_forward_kernel(a_ref, x_ref, c_ref, l_ref, u_ref, kty_ref,
                          tau_ref, xn_ref, kx_ref, acc_ref):
    """grid = (k, M/bm, N/bn); contracts over N, finishes on the last block.

    The x_new tail for column block j is (cheaply) recomputed at every row
    block i — deterministic, so the repeated xn writes all carry the same
    value — while the fresh x_new block feeds the accumulating matvec
    without ever leaving VMEM."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    tau = tau_ref[pl.program_id(0)]
    x_new = jnp.clip(x_ref[...] - tau * (c_ref[...] + kty_ref[...]),
                     l_ref[...], u_ref[...])
    xn_ref[...] = x_new.astype(xn_ref.dtype)
    acc_ref[...] += row_times_tile(x_new, a_ref[...].astype(jnp.float32))

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        kx_ref[...] = acc_ref[...].astype(kx_ref.dtype)


def _fused_backward_kernel(a_ref, y_ref, q_ref, mask_ref, kxn_ref, kxp_ref,
                           sig_ref, yn_ref, kty_ref, acc_ref):
    """grid = (k, N/bn, M/bm); contracts over M, finishes on the last block."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sigma = sig_ref[pl.program_id(0)]
    y_new = y_ref[...] + sigma * (2.0 * kxn_ref[...] - kxp_ref[...]
                                  - q_ref[...])
    y_new = jnp.where(mask_ref[...] != 0, jnp.maximum(y_new, 0.0), y_new)
    yn_ref[...] = y_new.astype(yn_ref.dtype)
    acc_ref[...] += row_times_tile_t(y_new, a_ref[...].astype(jnp.float32))

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        kty_ref[...] = acc_ref[...].astype(kty_ref.dtype)


_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def fused_forward_step(A, x, c, l, u, tau, kty, *,
                       block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                       interpret: bool = False):
    """Returns (x_new, kx).  A: [k, M, N]; x/c/l/u/kty: [k, N]; tau: [k]
    (per-sub-problem step size — POP sub-problems restart independently,
    so step sizes diverge across the batch)."""
    k, M, N = A.shape
    assert M % block_m == 0 and N % block_n == 0
    grid = (k, M // block_m, N // block_n)
    vec_n = lane_vec(block_n, 2)
    out = [jax.ShapeDtypeStruct((k, 1, N), jnp.float32),
           jax.ShapeDtypeStruct((k, 1, M), jnp.float32)]
    row = lambda v: v[:, None, :]
    xn, kx = pl.pallas_call(
        _fused_forward_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_m, block_n), lambda b, i, j: (b, i, j)),
            vec_n, vec_n, vec_n, vec_n, vec_n, _SMEM,
        ],
        out_specs=[vec_n, lane_vec(block_m, 1)],
        out_shape=out,
        scratch_shapes=[pltpu.VMEM((1, block_m), jnp.float32)],
        interpret=interpret,
    )(A, row(x), row(c), row(l), row(u), row(kty), tau.astype(jnp.float32))
    return xn[:, 0], kx[:, 0]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def fused_backward_step(A, y, q, sigma, ineq_mask, kx_new, kx_prev, *,
                        block_m: int = BLOCK_M, block_n: int = BLOCK_N,
                        interpret: bool = False):
    """Returns (y_new, kty).  A: [k, M, N]; y/q/kx_new/kx_prev: [k, M];
    ineq_mask: [k, M] bool; sigma: [k]."""
    k, M, N = A.shape
    assert M % block_m == 0 and N % block_n == 0
    grid = (k, N // block_n, M // block_m)
    vec_m = lane_vec(block_m, 2)
    out = [jax.ShapeDtypeStruct((k, 1, M), jnp.float32),
           jax.ShapeDtypeStruct((k, 1, N), jnp.float32)]
    row = lambda v: v[:, None, :]
    yn, kty = pl.pallas_call(
        _fused_backward_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_m, block_n), lambda b, j, i: (b, i, j)),
            vec_m, vec_m, vec_m, vec_m, vec_m, _SMEM,
        ],
        out_specs=[vec_m, lane_vec(block_n, 1)],
        out_shape=out,
        scratch_shapes=[pltpu.VMEM((1, block_n), jnp.float32)],
        interpret=interpret,
    )(A, row(y), row(q), row(ineq_mask.astype(jnp.int32)), row(kx_new),
      row(kx_prev), sigma.astype(jnp.float32))
    return yn[:, 0], kty[:, 0]
