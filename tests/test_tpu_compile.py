"""The step kernels of the TPU path, compiled for a described v5e chip.

Nothing here runs on a TPU.  Each test lowers one PDHG half-step the way
``kernels/ops.py`` dispatches it on a TPU, at paper-scale traffic
engineering widths, and compiles it for one chip of a ``v5e:2x2``
topology that the installed TPU compiler describes without the hardware.
That catches what interpret mode cannot: block shapes the chip's tiling
refuses, scoped-VMEM overruns and programs that outgrow the chip's HBM.

The structured widths are those of ``make_topology(754, seed=0)`` with
``make_demands(500_000, seed=1)`` and 4 paths per demand: the k=64 POP
stack (one lane per sub-problem) and the unpartitioned problem.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.pdhg import StructuredOperator
from repro.kernels import ops

V5E_HBM_BYTES = 16 * 10**9

# k=64 lane stack: rows = demands + links, columns = demands x 4 paths
LANE = dict(k=64, m=11_393, n=31_252, wr=32, ww=3_440, dr=2_118,
            wc=56, wv=8, dc=1)
# per 128-column chunk of the descending-width row bucket, the chunk's
# width over all lanes (``core/pdhg._wide_block_plan``)
LANE_ROW_PLAN_WIDTHS = (3_440, 984, 672, 504, 392, 312, 256, 208, 168, 144,
                        120, 96, 80, 72, 56, 48, 40)
# the unpartitioned problem (k=1)
FULL = dict(k=1, m=503_580, n=2_000_000, wr=32, ww=204_192, dr=3_347,
            wc=56, wv=8, dc=1)
# its row plan, as above
FULL_ROW_PLAN_WIDTHS = (
    204_192, 60_400, 40_744, 31_464, 24_664, 19_496, 15_600, 12_608,
    10_368, 8_504, 7_048, 5_752, 4_776, 3_984, 3_336, 2_712, 2_216, 1_792,
    1_448, 1_240, 1_032, 808, 656, 512, 336, 184, 56)


@pytest.fixture(scope="module")
def chip():
    """One chip of a described v5e:2x2, with the compile cache off (a
    compile for a described chip is written to the cache but cannot be
    read back without one)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _plan(d, widths):
    return tuple((c0, min(c0 + 128, d), wb)
                 for c0, wb in zip(range(0, d, 128), widths))


def _spec(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _operator(chip, d) -> StructuredOperator:
    """Shapes of a stacked f32 StructuredOperator with fold maps."""
    k = d["k"]
    f32 = lambda *s: _spec(chip, (k,) + s)
    i32 = lambda *s: _spec(chip, (k,) + s, jnp.int32)
    return StructuredOperator(
        row_idx=i32(d["wr"], d["m"]), row_val=f32(d["wr"], d["m"]),
        wrow_idx=i32(d["ww"], d["dr"]), wrow_val=f32(d["ww"], d["dr"]),
        wrow_ids=i32(d["dr"]),
        col_idx=i32(d["wc"], d["n"]), col_val=f32(d["wc"], d["n"]),
        wcol_idx=i32(d["wv"], d["dc"]), wcol_val=f32(d["wv"], d["dc"]),
        wcol_ids=i32(d["dc"]),
        row_fold=i32(d["m"]), col_fold=i32(d["n"]))


def _compile(fn, *args):
    """Compile for the described chip; the program must fit its HBM."""
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used / 1e9:.2f} GB exceeds the chip"
    return compiled


def _vectors(chip, k, n, count):
    return [_spec(chip, (k, n)) for _ in range(count)]


def _half_step_args(chip, d, direction):
    """(op, vectors..., step, ...) for a forward or backward half-step."""
    k, m, n = d["k"], d["m"], d["n"]
    op = _operator(chip, d)
    step = _spec(chip, (k,))
    if direction == "forward":          # x, c, l, u, tau, kty
        return (op, *_vectors(chip, k, n, 4), step, _spec(chip, (k, n)))
    return (op, *_vectors(chip, k, m, 2), step,     # y, q, sigma,
            _spec(chip, (k, m), jnp.bool_),         # ineq_mask,
            *_vectors(chip, k, m, 2))               # kx_new, kx_prev


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_lane_half_step_compiles(chip, direction):
    """The k-lane engine (``fused_structured``) at k=64, as dispatched on
    a TPU: the gather runs as XLA (``kernels/README.md``)."""
    assert ops.kernel_mode("gather") == "xla"
    if direction == "forward":
        plan = _plan(LANE["dr"], LANE_ROW_PLAN_WIDTHS)
        fn = lambda *a: ops.structured_forward_step(*a, plan=plan)
    else:
        plan = ((0, LANE["dc"], LANE["wv"]),)
        fn = lambda *a: ops.structured_backward_step(*a, plan=plan)
    _compile(fn, *_half_step_args(chip, LANE, direction))


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_full_half_step_compiles(chip, direction):
    """The streaming full engine (``fused_structured_full``) on the
    unpartitioned problem, with its ragged wide-block plan."""
    if direction == "forward":
        plan = _plan(FULL["dr"], FULL_ROW_PLAN_WIDTHS)
        fn = lambda *a: ops.structured_full_forward_step(*a, plan=plan)
    else:
        plan = ((0, FULL["dc"], FULL["wv"]),)
        fn = lambda *a: ops.structured_full_backward_step(*a, plan=plan)
    _compile(fn, *_half_step_args(chip, FULL, direction))


# (k, M, N) of dense operators the size of the traffic LPs: 3,000
# demands unpartitioned, and 20,000 demands at k=64, on the same topology
DENSE = [(1, 6_580, 12_000), (64, 3_893, 1_252)]


@pytest.mark.parametrize("k,m,n", DENSE)
@pytest.mark.parametrize("kernel", ["fused_forward_step",
                                    "fused_backward_step", "bmatvec",
                                    "bmatvec_t"])
def test_dense_kernel_compiles(chip, kernel, k, m, n):
    """The dense engine's Pallas kernels (``fused``, which a TPU picks for
    dense operators; its K/K^T products outside the loop), compiled as
    Mosaic kernels: what a TPU runs."""
    A = _spec(chip, (k, m, n))
    if kernel == "fused_forward_step":
        args = (A, *_vectors(chip, k, n, 4), _spec(chip, (k,)),
                _spec(chip, (k, n)))
    elif kernel == "fused_backward_step":
        args = (A, *_vectors(chip, k, m, 2), _spec(chip, (k,)),
                _spec(chip, (k, m), jnp.bool_), *_vectors(chip, k, m, 2))
    elif kernel == "bmatvec":
        args = (A, _spec(chip, (k, n)))
    else:
        args = (A, _spec(chip, (k, m)))
    fn = getattr(ops, kernel)
    compiled = _compile(lambda *a: fn(*a, backend="pallas"), *args)
    assert "tpu_custom_call" in compiled.as_text()
