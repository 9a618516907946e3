"""Fused two-operand elementwise reduction — the ``reduce_sum`` plugin.

Reference: kernels/plugins/reduce_sum/reduce_sum.cpp:27-97 streams two
512-bit operand lanes through a SIMD adder at line rate, one instance per
dtype (float/double/int32/int64/half). The TPU equivalent is a Pallas VPU
kernel: both operands are tiled HBM->VMEM, combined in one vector op, and
tiled back — XLA-fusable, bandwidth-bound, any dtype the VPU speaks.

``combine`` is the public entry: it pads/reshapes a flat operand pair to
the VPU tile geometry, runs the Pallas kernel on TPU (interpreter mode on
CPU so the same path is testable everywhere), and restores the shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.platform import pallas_interpret as _interpret

from ..constants import ReduceFunc

# lane count is fixed at 128 on TPU; 8 sublanes x 128 lanes is the fp32 tile
_LANES = 128
# flat operands reshape to (-1, _COLS): wider rows give the DMA engine long
# contiguous transfers (measured on v5e: 128-col tiles cost ~4% bandwidth)
_COLS = 1024
_BLOCK_ROWS = 512  # rows per grid step (512x1024 fp32 = 2 MiB per operand)

_FUNCS = {
    ReduceFunc.SUM: jnp.add,
    ReduceFunc.MAX: jnp.maximum,
    ReduceFunc.MIN: jnp.minimum,
    ReduceFunc.PROD: jnp.multiply,
}


# dtypes the Mosaic TPU dialect handles natively; anything else (f16, f64,
# i64 — present in the reference's per-dtype plugin set) falls back to the
# plain XLA elementwise op, which is the same VPU instruction stream anyway.
_MOSAIC_DTYPES = frozenset(map(jnp.dtype, (
    jnp.float32, jnp.bfloat16, jnp.int32, jnp.int8,
    jnp.float8_e4m3fn, jnp.float8_e5m2)))


def _pallas_ok(*dtypes) -> bool:
    if _interpret():
        return True
    return all(jnp.dtype(d) in _MOSAIC_DTYPES for d in dtypes)


def _combine_kernel(a_ref, b_ref, o_ref, *, func: ReduceFunc):
    o_ref[:] = _FUNCS[func](a_ref[:], b_ref[:])


@functools.partial(jax.jit, static_argnames=("func",))
def combine_pallas(a: jax.Array, b: jax.Array,
                   func: ReduceFunc = ReduceFunc.SUM) -> jax.Array:
    """Pallas kernel over 2-D (rows, 128k) tiles. Inputs must already be
    tile-shaped; use :func:`combine` for arbitrary shapes."""
    assert a.shape == b.shape and a.ndim == 2, (a.shape, b.shape)
    rows, cols = a.shape
    block = (min(_BLOCK_ROWS, rows), cols)
    grid = (pl.cdiv(rows, block[0]),)
    return pl.pallas_call(
        functools.partial(_combine_kernel, func=func),
        out_shape=jax.ShapeDtypeStruct(a.shape, a.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        # result reuses op0's buffer (the reference's res-over-op0 stream
        # reuse, res_as_op0). Measured on v5e: without the alias the output
        # DMA stops overlapping the input stream and the kernel drops from
        # ~700 to ~400 GB/s; XLA inserts a defensive copy when the caller
        # still holds op0, so semantics stay functional.
        input_output_aliases={0: 0},
        interpret=_interpret(),
    )(a, b)


def combine(a: jax.Array, b: jax.Array,
            func: ReduceFunc = ReduceFunc.SUM) -> jax.Array:
    """res = func(a, b) elementwise, any shape/dtype, via the Pallas lane.

    The combine dataplane of the reference's `combine`/fused-reduce ops
    (ccl_offload_control.c:319-335 routing into the reduce plugin).
    """
    assert a.shape == b.shape, (a.shape, b.shape)
    if not _pallas_ok(a.dtype, b.dtype):
        return _FUNCS[func](a, b)
    shape = a.shape
    flat_a = a.reshape(-1)
    flat_b = b.reshape(-1)
    n = flat_a.size
    cols = _COLS if n >= _COLS else _LANES
    pad = (-n) % cols
    if pad:
        flat_a = jnp.pad(flat_a, (0, pad))
        flat_b = jnp.pad(flat_b, (0, pad))
    out = combine_pallas(flat_a.reshape(-1, cols),
                         flat_b.reshape(-1, cols), func)
    out = out.reshape(-1)
    if pad:
        out = out[:n]
    return out.reshape(shape)
