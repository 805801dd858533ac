"""Pallas TPU kernels: batched constraint-matrix matvecs for the PDHG loop.

The PDHG inner loop is two matvecs per iteration over the stacked
constraint matrix K — for POP, a *batched* K of shape [k_subproblems, M, N].
These kernels tile K into MXU-aligned VMEM blocks and accumulate partial
products in VMEM, so each K element is read from HBM exactly once per
matvec (the roofline for this op — it is memory-bound at PDHG's 2 flops
per byte).

Tiling scheme (forward ``bmatvec``):

    grid = (k, M/bm, N/bn)            # N is the reduction axis
    A block  : (bm, bn)     VMEM      (lane axis squeezed)
    x block  : (1, bn)      VMEM      (re-read per M row-block: bn << HBM)
    y block  : (1, bm)      VMEM      accumulated across the N axis

Vectors travel as ``[k, 1, n]`` views with the lane axis squeezed out of
the block, so the last two block dims are ``(1, bn)``: the second-minor
dim equals the array's, which the TPU's block-shape rule requires (a
``(1, bn)`` block over ``[k, n]`` is refused for every ``k > 1``).

The transposed matvec reads the SAME layout of K (no materialised K^T in
HBM — a [k,M,N]-strided transpose would double memory traffic) and
contracts along M instead, transposing only the (bm, bn) tile in VMEM,
which the MXU handles natively via ``dot_general`` dimension numbers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# MXU-aligned defaults: 256x256 f32 tile = 256 KiB VMEM for the K block,
# well inside the ~16 MiB/core VMEM budget with double buffering.
BLOCK_M = 256
BLOCK_N = 256


# row-vector x tile products on the MXU; f32 accumulation at full precision
_DOT = dict(preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)


def row_times_tile(v, a):
    """(1, bn) x (bm, bn) -> (1, bm): v @ a^T, contracting the lane axes."""
    return jax.lax.dot_general(v, a, (((1,), (1,)), ((), ())), **_DOT)


def row_times_tile_t(v, a):
    """(1, bm) x (bm, bn) -> (1, bn): v @ a, contracting over bm."""
    return jax.lax.dot_general(v, a, (((1,), (0,)), ((), ())), **_DOT)


def lane_vec(block: int, axis_of: int) -> pl.BlockSpec:
    """BlockSpec of a ``[k, 1, n]`` vector view: the lane axis (grid axis
    0) squeezed, ``block`` wide, stepped by grid axis ``axis_of``."""
    return pl.BlockSpec((None, 1, block),
                        lambda *g: (g[0], 0, g[axis_of]))


def _bmatvec_kernel(a_ref, x_ref, o_ref):
    """One (bm, bn) tile: o[bm] += A[bm, bn] @ x[bn]."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += row_times_tile(x_ref[...], a_ref[...]).astype(o_ref.dtype)


def _bmatvec_t_kernel(a_ref, y_ref, o_ref):
    """One (bm, bn) tile: o[bn] += A[bm, bn]^T @ y[bm] (in-VMEM transpose)."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += row_times_tile_t(y_ref[...], a_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def bmatvec(A: jnp.ndarray, x: jnp.ndarray, *,
            block_m: int = BLOCK_M, block_n: int = BLOCK_N,
            interpret: bool = False) -> jnp.ndarray:
    """y[k, M] = A[k, M, N] @ x[k, N].  Shapes must be block-divisible
    (``ops.py`` handles padding)."""
    k, M, N = A.shape
    assert M % block_m == 0 and N % block_n == 0, (M, N, block_m, block_n)
    grid = (k, M // block_m, N // block_n)
    y = pl.pallas_call(
        _bmatvec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_m, block_n), lambda b, i, j: (b, i, j)),
            lane_vec(block_n, 2),
        ],
        out_specs=lane_vec(block_m, 1),
        out_shape=jax.ShapeDtypeStruct((k, 1, M), jnp.float32),
        interpret=interpret,
    )(A, x[:, None, :])
    return y[:, 0]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "interpret"))
def bmatvec_t(A: jnp.ndarray, y: jnp.ndarray, *,
              block_m: int = BLOCK_M, block_n: int = BLOCK_N,
              interpret: bool = False) -> jnp.ndarray:
    """x[k, N] = A[k, M, N]^T @ y[k, M] without materialising A^T."""
    k, M, N = A.shape
    assert M % block_m == 0 and N % block_n == 0, (M, N, block_m, block_n)
    # reduction axis is M now -> make it the innermost grid dim
    grid = (k, N // block_n, M // block_m)
    x = pl.pallas_call(
        _bmatvec_t_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_m, block_n), lambda b, j, i: (b, i, j)),
            lane_vec(block_m, 2),
        ],
        out_specs=lane_vec(block_n, 1),
        out_shape=jax.ShapeDtypeStruct((k, 1, N), jnp.float32),
        interpret=interpret,
    )(A, y[:, None, :])
    return x[:, 0]
