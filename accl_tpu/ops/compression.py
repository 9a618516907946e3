"""Wire-precision compression lanes.

Reference: kernels/plugins/fp_hp_stream_conv (fp32 -> fp16, 2 words in, 1
word out) and hp_fp_stream_conv (fp16 -> fp32) are dedicated dataplane
lanes the dma_mover routes operands through when a call carries
OP*/RES/ETH_COMPRESSED flags (dma_mover.cpp:44-168). Here each lane is a
Pallas cast kernel plus, beyond the reference, a *scaled fp8* codec
(per-tensor max-abs scaling, the EQuARX-style quantized-collective lane)
for 4x wire compression.

The collectives dataplane (parallel.collectives) applies these around each
``ppermute`` hop; the driver's flag algebra (accl.ACCL._prepare) decides
when.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.platform import pallas_interpret as _interpret

_LANES = 128
# flat payloads reshape to (-1, _COLS) like the combine dataplane: wider
# rows mean 8x fewer grid steps, which is the difference between a
# grid-overhead-bound lane and an HBM-bound one at large sizes
_COLS = 1024
_BLOCK_ROWS = 512  # 512x1024 fp32 = 2 MiB per block


def _cast_kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:].astype(o_ref.dtype)


def _tiled(x: jax.Array):
    """Flatten + pad to (rows, cols) tile geometry (1024-wide when the
    payload allows, 128 lanes minimum); returns (tiles, n, pad)."""
    flat = x.reshape(-1)
    n = flat.size
    cols = _COLS if n >= _COLS else _LANES
    pad = (-n) % cols
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, cols), n, pad


def _untiled(tiles: jax.Array, n: int, shape) -> jax.Array:
    out = tiles.reshape(-1)
    if out.size != n:
        out = out[:n]
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("dtype",))
def _cast_tiles(x: jax.Array, dtype) -> jax.Array:
    rows, cols = x.shape
    block = (min(_BLOCK_ROWS, rows), cols)
    grid = (pl.cdiv(rows, block[0]),)
    return pl.pallas_call(
        _cast_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, dtype),
        grid=grid,
        in_specs=[pl.BlockSpec(block, lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        name="accl_cast",
        interpret=_interpret(),
    )(x)


def cast_lane(x: jax.Array, dtype) -> jax.Array:
    """Streamed dtype cast (both conversion directions; the down/up lanes
    of the reference are the dtype-ordered pair of calls)."""
    from .combine import _pallas_ok
    dtype = jnp.dtype(dtype)
    if x.dtype == dtype:
        return x
    if not _pallas_ok(x.dtype, dtype):
        return x.astype(dtype)
    tiles, n, _ = _tiled(x)
    return _untiled(_cast_tiles(tiles, dtype), n, x.shape)


# ---------------------------------------------------------------------------
# Scaled fp8 codec (per-tensor max-abs scale)
# ---------------------------------------------------------------------------

FP8 = jnp.float8_e4m3fn
_FP8_MAX = 448.0  # finfo max of e4m3fn

# names the collectives dataplane recognizes as scaled-codec wire dtypes
FP8_DTYPE_NAMES = ("float8_e4m3fn", "float8_e5m2")


def fp8_quantize(x: jax.Array, wire_dtype,
                 axes: tuple[int, ...] | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """THE scaled-fp8 wire policy, as pure jnp — shard-safe (traceable
    inside shard_map ring loops, where XLA fuses it into the ppermute's
    producers) and bitwise-identical to the Pallas codec below (both
    multiply by the reciprocal scale).

    scale = max(amax / finfo(wire).max, 1e-30); ``axes=None`` gives one
    per-tensor scale, a tuple gives an amax over those axes (the
    per-(rank, chunk) scales of the fused reduce-scatter path).
    Returns (fp8 payload, fp32 scale)."""
    xf = x.astype(jnp.float32)
    fp8_max = float(jnp.finfo(wire_dtype).max)
    amax = (jnp.max(jnp.abs(xf)) if axes is None
            else jnp.max(jnp.abs(xf), axis=axes))
    scale = jnp.maximum(amax / fp8_max, 1e-30)
    bshape = scale.shape + (1,) * (xf.ndim - scale.ndim)
    q = (xf * (1.0 / scale).reshape(bshape)).astype(wire_dtype)
    return q, scale


def fp8_dequantize(q: jax.Array, scale: jax.Array,
                   dtype=jnp.float32) -> jax.Array:
    """Inverse of :func:`fp8_quantize`; broadcasts the scale over the
    payload's trailing axes."""
    bshape = scale.shape + (1,) * (q.ndim - scale.ndim)
    return (q.astype(jnp.float32)
            * scale.reshape(bshape)).astype(dtype)


def _quant_kernel(x_ref, inv_ref, o_ref):
    o_ref[:] = (x_ref[:] * inv_ref[0, 0]).astype(o_ref.dtype)


def _dequant_kernel(q_ref, scale_ref, o_ref):
    o_ref[:] = q_ref[:].astype(o_ref.dtype) * scale_ref[0, 0]


@functools.partial(jax.jit, static_argnames=("wire_dtype",))
def compress_fp8(x: jax.Array, wire_dtype=FP8
                 ) -> tuple[jax.Array, jax.Array]:
    """x (float) -> (fp8 payload, fp32 scale). Pallas-kernel form of
    :func:`fp8_quantize` for the standalone lane (same scale policy, same
    reciprocal-multiply rounding; the (1,1) scale rides the wire alongside
    the payload, 4 bytes per message). ``wire_dtype`` picks the fp8
    flavor (e4m3fn default, e5m2 for the wide-range lane)."""
    tiles, n, _ = _tiled(x)
    amax = jnp.max(jnp.abs(tiles.astype(jnp.float32)))
    scale = jnp.maximum(amax / float(jnp.finfo(wire_dtype).max), 1e-30)
    inv = (1.0 / scale).reshape(1, 1)
    rows, cols = tiles.shape
    block = (min(_BLOCK_ROWS, rows), cols)
    q = pl.pallas_call(
        _quant_kernel,
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.dtype(wire_dtype)),
        grid=(pl.cdiv(rows, block[0]),),
        in_specs=[
            pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        name="accl_fp8_quant",
        interpret=_interpret(),
    )(tiles.astype(jnp.float32), inv)
    return q.reshape(-1)[:n].reshape(x.shape), scale.reshape(1, 1)


@functools.partial(jax.jit, static_argnames=("dtype",))
def decompress_fp8(q: jax.Array, scale: jax.Array,
                   dtype=jnp.float32) -> jax.Array:
    tiles, n, _ = _tiled(q)
    rows, cols = tiles.shape
    block = (min(_BLOCK_ROWS, rows), cols)
    out = pl.pallas_call(
        _dequant_kernel,
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.dtype(dtype)),
        grid=(pl.cdiv(rows, block[0]),),
        in_specs=[
            pl.BlockSpec(block, lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(block, lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        name="accl_fp8_dequant",
        interpret=_interpret(),
    )(tiles, scale.reshape(1, 1).astype(jnp.float32))
    return _untiled(out, n, q.shape)


# ---------------------------------------------------------------------------
# Block-scaled quantized codec (the device twin of accl_tpu/quant.py)
# ---------------------------------------------------------------------------
#
# Per-block absmax scaling, bit-identical to the numpy reference
# (quant._np_quantize/_np_dequant — the contract the native codec is
# also held to, tests/test_pallas_quant.py pins this twin the same way):
#
#   amax  = max(|x|) per block (NaN-propagating)
#   scale = amax / qmax, clamped to 1.0 unless positive-normal-finite
#   q     = cast(x * (1/scale))  — RNE; e4m3fn overflows to NaN, e5m2
#           to inf, int8 rounds half-to-even / clips / zeros non-finite
#   x'    = float32(q) * scale   — one f32 rounding
#
# The fused combine kernel runs dequant -> f32 accumulate -> requant in
# ONE VMEM pass per row block: the f32 partial exists only inside the
# kernel, never as a materialized wire buffer. Fresh scales come out of
# every hop, so per-hop error stays bounded and never compounds through
# the accumulator (the PR 15 quantized-wire contract).

from ..constants import ReduceFunc as _RF

# the quantizable wire dtypes + their reference constants, from THE
# numpy reference module so the two lanes cannot drift
from .. import quant as _quant

BS_WIRE_DTYPE_NAMES = tuple(_quant._QCODES)   # int8 + e4m3fn + e5m2

# smallest normal f32, as a python float so kernels inline it as a
# literal (a jnp scalar would be a captured constant pallas rejects)
_BS_FLT_MIN = float(_quant._FLT_MIN)

_BS_COMBINE = {
    _RF.SUM: jnp.add,
    _RF.MAX: jnp.maximum,
    _RF.MIN: jnp.minimum,
    _RF.PROD: jnp.multiply,
}


# f32 -> fp8 cast parameters, empirically pinned against ml_dtypes
# (quant.py's reference cast). XLA's own f32->f8 convert double-rounds
# through f16 on CPU (e.g. -367.993 -> f16 -368 -> RNE tie -> -384 where
# ml_dtypes' single rounding gives -352), so the kernels encode in
# integer bit-math instead. Per dtype:
#   (mantissa shift, exponent rebias in code units, min-normal f32 bits,
#    clamp code, denormal scale 2^(bias+mant-1), NaN code or None)
# e4m3fn needs no NaN case: rounding overflow, inf and NaN all clamp
# into 0x7f — exactly ml_dtypes' inf->NaN saturation. e5m2 overflow
# clamps to inf 0x7c while true NaNs take the canonical 0x7e, sign kept.
_BS_FP8 = {
    "float8_e4m3fn": (20, 960, 0x3C800000, 0x7F, 512.0, None),
    "float8_e5m2": (21, 448, 0x38800000, 0x7C, 65536.0, 0x7E),
}


def _bs_fp8_cast(v: jax.Array, qname: str,
                 sign_of: jax.Array | None = None) -> jax.Array:
    """Bit-exact ml_dtypes RNE f32 -> fp8 encode (see _BS_FP8). The
    code's sign bit comes from ``sign_of`` (default ``v``): a caller
    that encodes ``x * inv`` with ``inv > 0`` passes ``x``. The product
    has x's sign, except that IEEE leaves a NaN product's sign open
    (numpy's keeps x's; a backend need not).

    The bit math runs in int32 with logical shifts: Mosaic legalizes
    neither unsigned min nor 8-bit vector shifts, and int32 wraps on
    the one overflow (NaN payloads + the rounding bias) into the same
    bit pattern a uint32 would hold, which the logical shift reads back
    as the unsigned value."""
    shift, rebias, nmin, clamp, dscale, nan_code = _BS_FP8[qname]
    srl = jax.lax.shift_right_logical
    u = jax.lax.bitcast_convert_type(v, jnp.int32)
    sign = srl(jax.lax.bitcast_convert_type(
        v if sign_of is None else sign_of, jnp.int32), 31) << 7
    a = u & 0x7FFFFFFF
    # normals/overflow: integer round-nearest-even of the top mantissa
    # bits, exponent rebiasing folded into the code arithmetic; rounding
    # carries ripple into the exponent field for free
    lsb = srl(a, shift) & 1
    rne = srl(a + ((1 << (shift - 1)) - 1) + lsb, shift)
    code = jnp.minimum(rne - rebias, clamp)
    # target denormals: scale into code units (exact, power of two) and
    # RNE in f32 — jnp.round is half-to-even
    code_d = jnp.round(jnp.abs(v) * jnp.float32(dscale)).astype(jnp.int32)
    code = jnp.where(a < nmin, code_d, code)
    if nan_code is not None:
        code = jnp.where(a > 0x7F800000, nan_code, code)
    bits = (sign | code).astype(jnp.int8)
    return jax.lax.bitcast_convert_type(bits, jnp.dtype(qname))


def _bs_encode(v: jax.Array, sign_of: jax.Array, qdtype) -> jax.Array:
    """f32 -> wire cast with the reference's saturation rules. fp8 rides
    the bit-exact encoder above (RNE; e4m3fn overflow -> NaN, e5m2 ->
    inf, the ml_dtypes semantics); int8 rounds half-to-even, clips to
    +-127 and zeroes non-finite values."""
    if jnp.dtype(qdtype) == jnp.int8:
        return jnp.where(jnp.isfinite(v),
                         jnp.clip(jnp.round(v), -127.0, 127.0),
                         jnp.float32(0.0)).astype(jnp.int8)
    return _bs_fp8_cast(v, jnp.dtype(qdtype).name, sign_of)


def _bs_div(a: jax.Array, b: jax.Array) -> jax.Array:
    """IEEE round-to-nearest-even ``a / b``, exact on every backend.

    A TPU's f32 divide is not correctly rounded (its scales came out up
    to 3 ULP off the numpy reference on a v5e), so the per-block scale
    and its reciprocal are divided here: the backend's own quotient of
    the 24-bit significands only estimates the 25-bit quotient, which
    is then corrected against the exact integer remainder (12-bit limbs
    keep every product inside int32) and rounded half-even on the guard
    and sticky bits. Exact for a >= 0 normal and b > 0 normal finite,
    subnormal and overflowing quotients included; inf and NaN ``a``
    pass through and a subnormal ``a`` gives 0."""
    srl = jax.lax.shift_right_logical
    ia = jax.lax.bitcast_convert_type(a, jnp.int32)
    ib = jax.lax.bitcast_convert_type(b, jnp.int32)
    ea, eb = srl(ia, 23), srl(ib, 23)
    ma = (ia & 0x7FFFFF) | 0x800000
    mb = (ib & 0x7FFFFF) | 0x800000
    lt = (ma < mb).astype(jnp.int32)
    m = ma << lt                        # m / mb in [1, 2)
    e = ea - eb + 127 - lt              # biased exponent of the quotient
    bf = mb.astype(jnp.float32)
    q = (m.astype(jnp.float32) / bf * jnp.float32(1 << 24)).astype(jnp.int32)
    # r = m * 2^24 - q * mb, exactly: the true r is a few mb at most, so
    # the 2^24 limb difference is tiny and nothing overflows
    qh, ql = srl(q, 12), q & 0xFFF
    bh, bl = srl(mb, 12), mb & 0xFFF
    mid = qh * bl + ql * bh
    r = (((m - qh * bh - srl(mid, 12)) << 24) - ((mid & 0xFFF) << 12)
         - ql * bl)
    k = jnp.floor(r.astype(jnp.float32) / bf).astype(jnp.int32)
    q, r = q + k, r - k * mb
    q, r = jnp.where(r < 0, q - 1, q), jnp.where(r < 0, r + mb, r)
    q, r = jnp.where(r >= mb, q + 1, q), jnp.where(r >= mb, r - mb, r)
    # keep 24 bits for a normal quotient, fewer for a subnormal one
    drop = jnp.clip(2 - e, 1, 26)
    kept = srl(q, drop)
    guard = srl(q, drop - 1) & 1
    sticky = ((q & ((1 << (drop - 1)) - 1)) != 0) | (r != 0)
    kept = kept + (guard & (sticky.astype(jnp.int32) | (kept & 1)))
    bits = jnp.where(e >= 1, ((e - 1) << 23) + kept, kept)
    bits = jnp.where(e > 254, 0x7F800000, bits)     # overflow -> inf
    out = jax.lax.bitcast_convert_type(bits, jnp.float32)
    out = jnp.where(ea == 0, jnp.float32(0.0), out)
    return jnp.where(ea == 255, a, out)


def _bs_quant_rows(x: jax.Array, qdtype, one: jax.Array,
                   qmax: jax.Array):
    """Shared quantize body: x (R, block) f32 -> (q, scales (R, 1)).

    ``one``/``qmax`` are RUNTIME scalars (SMEM operands), not literals,
    so no compiler folds the scale arithmetic into constants; the two
    divisions are exact (:func:`_bs_div`), as numpy's are."""
    amax = jnp.max(jnp.abs(x), axis=1, keepdims=True)  # NaN-propagating
    s = _bs_div(amax, jnp.broadcast_to(qmax, amax.shape))
    good = (s >= _BS_FLT_MIN) & (s < jnp.inf)
    s = jnp.where(good, s, jnp.float32(1.0))
    inv = _bs_div(jnp.broadcast_to(one, s.shape), s)
    v = x * inv                         # reciprocal-multiply, like numpy
    return _bs_encode(v, x, qdtype), s


def _bs_block_rows(block: int) -> int:
    """Rows per grid step: ~1 MiB of f32 per VMEM block, the largest at
    which the quantize kernel compiles for a v5e: at 2 MiB (4096 rows
    at block 128) its scale math (:func:`_bs_div`, (R, 1) columns each
    padded to whole (8, 128) tiles) needs 4 KiB over the 16 MiB scoped
    VMEM (``scripts/codec_rows_compile.py``). The dequantize and combine
    kernels would compile at 2 MiB, but all three share one payload
    geometry (:func:`_bs_geometry`), and dequantize ran no slower at
    1 MiB on the chip."""
    return max(8, (1 << 20) // (4 * block))


def _bs_geometry(n: int, block: int) -> tuple[int, int, int]:
    """(nb, row_block, padded_rows): blocks-per-payload, grid row chunk
    (:func:`_bs_block_rows`), and nb padded up to a multiple of
    the chunk so every grid step sees a full block (padded rows are
    zeros -> scale 1.0, payload 0; sliced off after the call)."""
    nb = -(-n // block)
    rows = _bs_block_rows(block)
    rows = min(rows, nb) if nb >= 8 else nb
    return nb, rows, nb + ((-nb) % rows)


def _bs_pad_rows(tiles: jax.Array, nb: int, rows_padded: int,
                 fill: float = 0.0) -> jax.Array:
    if rows_padded != nb:
        tiles = jnp.pad(tiles, ((0, rows_padded - nb), (0, 0)),
                        constant_values=fill)
    return tiles


def _bs_tiles(x: jax.Array, block: int, nb: int,
              rows_padded: int) -> jax.Array:
    """Flatten + zero-pad a payload to (rows_padded, block) f32 rows."""
    flat = x.reshape(-1).astype(jnp.float32)
    pad = nb * block - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return _bs_pad_rows(flat.reshape(nb, block), nb, rows_padded)


def _bs_scalars(qname: str) -> tuple[jax.Array, jax.Array]:
    """(one, qmax) as (1, 1) f32 runtime operands (see _bs_quant_rows).

    These must be built EAGERLY (outside any trace) and enter every
    jitted program as ARGUMENTS: created inside a trace they become
    compile-time constants, and then LLVM folds the ``* one`` guard and
    contracts dequant-multiply + accumulate into an fma — 1 ULP off the
    numpy reference. (The divisions no longer depend on it: _bs_div
    corrects its quotient exactly.) (optimization_barrier does not
    help: constants still reach LLVM as immediates.) The bs_* wrappers
    build them eagerly per call; the ring collective programs thread
    them through shard_map as replicated inputs."""
    return (jnp.float32(1.0).reshape(1, 1),
            jnp.float32(_quant._QMAX[qname]).reshape(1, 1))


@functools.partial(jax.jit, static_argnames=("qname", "block"))
def _bs_quant_call(tiles: jax.Array, one: jax.Array, qmax: jax.Array,
                   qname: str, block: int):
    """tiles: (rows_padded, block) f32 -> (q tiles, scales (rows, 1))."""
    qdtype = jnp.dtype(qname)
    rows = tiles.shape[0]

    def kernel(x_ref, one_ref, qmax_ref, q_ref, s_ref):
        q, s = _bs_quant_rows(x_ref[:], qdtype, one_ref[0, 0],
                              qmax_ref[0, 0])
        q_ref[:] = q
        s_ref[:] = s

    R = min(_bs_block_rows(block), rows)
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct(tiles.shape, qdtype),
                   jax.ShapeDtypeStruct((rows, 1), jnp.float32)),
        grid=(pl.cdiv(rows, R),),
        in_specs=[pl.BlockSpec((R, block), lambda i: (i, 0),
                               memory_space=pltpu.VMEM), smem, smem],
        out_specs=(pl.BlockSpec((R, block), lambda i: (i, 0),
                                memory_space=pltpu.VMEM),
                   pl.BlockSpec((R, 1), lambda i: (i, 0),
                                memory_space=pltpu.VMEM)),
        name="accl_bs_quant",
        interpret=_interpret(),
    )(tiles, one, qmax)


@functools.partial(jax.jit, static_argnames=("block",))
def _bs_dequant_call(qtiles: jax.Array, scales: jax.Array, block: int):
    """(rows, block) wire tiles + (rows, 1) scales -> f32 tiles."""
    rows = qtiles.shape[0]

    def kernel(q_ref, s_ref, o_ref):
        o_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:]

    R = min(_bs_block_rows(block), rows)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(qtiles.shape, jnp.float32),
        grid=(pl.cdiv(rows, R),),
        in_specs=[pl.BlockSpec((R, block), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((R, 1), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((R, block), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        name="accl_bs_dequant",
        interpret=_interpret(),
    )(qtiles, scales)


@functools.partial(jax.jit, static_argnames=("func", "qname", "block",
                                             "requant"))
def _bs_combine_call(qtiles: jax.Array, scales: jax.Array,
                     other: jax.Array, one: jax.Array, qmax: jax.Array,
                     func: _RF, qname: str, block: int,
                     requant: bool):
    """The fused dequant -> f32-accumulate [-> requant] kernel: one VMEM
    pass per row block, accumulation entirely in f32 registers — the
    partial is never written back at full width. ``requant=True``
    returns fresh (q', scales') for the next hop; False returns the f32
    result (the final hop of a ring round)."""
    qdtype = jnp.dtype(qname)
    rows = qtiles.shape[0]
    op = _BS_COMBINE[func]

    def kernel(q_ref, s_ref, x_ref, one_ref, qmax_ref, *out_refs):
        # the extra `* one` pins the dequant product to its own rounding:
        # XLA contracts `x + q*s` into an fma (single rounding, 1 ULP off
        # the reference's dequant-then-add); `x + (q*s)*one` can only
        # contract the exact *1.0 step, so `q*s` stays materialized
        deq = (q_ref[:].astype(jnp.float32) * s_ref[:]) * one_ref[0, 0]
        acc = op(x_ref[:], deq)
        if requant:
            q2, s2 = _bs_quant_rows(acc, qdtype, one_ref[0, 0],
                                    qmax_ref[0, 0])
            out_refs[0][:] = q2
            out_refs[1][:] = s2
        else:
            out_refs[0][:] = acc

    R = min(_bs_block_rows(block), rows)
    row_spec = pl.BlockSpec((R, block), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    s_spec = pl.BlockSpec((R, 1), lambda i: (i, 0),
                          memory_space=pltpu.VMEM)
    smem = pl.BlockSpec((1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
    if requant:
        out_shape = (jax.ShapeDtypeStruct(qtiles.shape, qdtype),
                     jax.ShapeDtypeStruct((rows, 1), jnp.float32))
        out_specs = (row_spec, s_spec)
    else:
        out_shape = jax.ShapeDtypeStruct(qtiles.shape, jnp.float32)
        out_specs = row_spec
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        grid=(pl.cdiv(rows, R),),
        in_specs=[row_spec, s_spec, row_spec, smem, smem],
        out_specs=out_specs,
        name=("accl_bs_combine_requant" if requant
              else "accl_bs_combine"),
        interpret=_interpret(),
    )(qtiles, scales, other, one, qmax)


def bs_quantize(x: jax.Array, wire_dtype, block: int, scalars=None
                ) -> tuple[jax.Array, jax.Array]:
    """Block-scale quantize a payload: (q ``x.shape`` in the wire dtype,
    scales (nb,) f32), nb = ceil(n / block). The on-wire footprint is
    exactly the packed segment's scales+data region (quant.packed_nbytes
    minus the header — the header is host-tier framing). ``scalars``:
    optional eager (one, qmax) pair from :func:`_bs_scalars` — callers
    tracing this under their own jit must pass it through as program
    arguments to keep bit-identity (see _bs_scalars)."""
    n = int(jnp.size(x))
    nb, _, rows_padded = _bs_geometry(n, block)
    tiles = _bs_tiles(x, block, nb, rows_padded)
    qname = jnp.dtype(wire_dtype).name
    one, qmax = scalars if scalars is not None else _bs_scalars(qname)
    q, s = _bs_quant_call(tiles, one, qmax, qname, block)
    return (q.reshape(-1)[:n].reshape(x.shape), s.reshape(-1)[:nb])


def bs_dequantize(q: jax.Array, scales: jax.Array, block: int
                  ) -> jax.Array:
    """Inverse of :func:`bs_quantize`: f32, one rounding per element."""
    n = int(jnp.size(q))
    nb, _, rows_padded = _bs_geometry(n, block)
    flat = q.reshape(-1)
    pad = nb * block - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    qtiles = _bs_pad_rows(flat.reshape(nb, block), nb, rows_padded)
    s = _bs_pad_rows(scales.reshape(nb, 1), nb, rows_padded, fill=1.0)
    out = _bs_dequant_call(qtiles, s, block)
    return out.reshape(-1)[:n].reshape(q.shape)


def _bs_combine_tiles(q: jax.Array, scales: jax.Array, other: jax.Array,
                      block: int):
    n = int(jnp.size(q))
    nb, _, rows_padded = _bs_geometry(n, block)
    flat = q.reshape(-1)
    pad = nb * block - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    qtiles = _bs_pad_rows(flat.reshape(nb, block), nb, rows_padded)
    s = _bs_pad_rows(scales.reshape(nb, 1), nb, rows_padded, fill=1.0)
    x = _bs_tiles(other, block, nb, rows_padded)
    return qtiles, s, x, n, nb


def bs_combine_requant(q: jax.Array, scales: jax.Array, other: jax.Array,
                       func: _RF, wire_dtype, block: int, scalars=None
                       ) -> tuple[jax.Array, jax.Array]:
    """One quantized ring hop: ``func(other, dequant(q, scales))`` in f32
    and requantized against FRESH per-block scales, fused in one VMEM
    pass (matches quant.dequant_combine_packed + quantize_packed run
    back to back, bit-identically). Returns (q', scales')."""
    qtiles, s, x, n, nb = _bs_combine_tiles(q, scales, other, block)
    qname = jnp.dtype(wire_dtype).name
    one, qmax = scalars if scalars is not None else _bs_scalars(qname)
    q2, s2 = _bs_combine_call(qtiles, s, x, one, qmax, _RF(func),
                              qname, block, True)
    return (q2.reshape(-1)[:n].reshape(q.shape), s2.reshape(-1)[:nb])


def bs_dequant_combine(q: jax.Array, scales: jax.Array, other: jax.Array,
                       func: _RF, block: int, scalars=None) -> jax.Array:
    """The final hop's fused step: ``func(other, dequant(q, scales))``
    in f32, no requantization (the ring's round-closing combine —
    quant.dequant_combine_packed's numerics)."""
    qtiles, s, x, n, _ = _bs_combine_tiles(q, scales, other, block)
    wd = q.dtype.name if q.dtype.name in BS_WIRE_DTYPE_NAMES else "int8"
    one, qmax = scalars if scalars is not None else _bs_scalars(wd)
    out = _bs_combine_call(qtiles, s, x, one, qmax, _RF(func),
                           wd, block, False)
    return out.reshape(-1)[:n].reshape(other.shape)


# ---------------------------------------------------------------------------
# Wire codec dispatch — what a collective hop calls
# ---------------------------------------------------------------------------

def wire_compress(x: jax.Array, wire_dtype):
    """Encode a hop payload for the wire. Returns (payload, aux) where aux
    is the fp8 scale or None. Cast lanes for fp16/bf16; scaled codec for
    fp8 dtypes."""
    wd = jnp.dtype(wire_dtype)
    if wd == x.dtype:
        return x, None
    if wd.name in FP8_DTYPE_NAMES:
        return compress_fp8(x, wire_dtype=wd)
    return cast_lane(x, wd), None


def wire_decompress(payload: jax.Array, aux, dtype) -> jax.Array:
    if aux is not None:
        return decompress_fp8(payload, aux, dtype)
    return cast_lane(payload, dtype)
