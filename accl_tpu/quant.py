"""Block-scaled quantized wire codec (EQuARX-style, arXiv 2506.17615).

The eth-compression lane's second gear: where plain dtype narrowing
(``Compression.ETH_COMPRESSED``) casts every wire element to the
compressed dtype, **block-scaled quantization**
(``Compression.BLOCK_SCALED``) sends each segment as a compact header +
one f32 scale per ``block`` elements + the fp8/int8 payload, recovering
most of the narrow dtype's lost dynamic range: every block is quantized
against its own absmax, so a segment mixing tiny gradients with large
ones keeps ~2-3 effective extra bits over a single global cast.

Wire layout of one block-scaled segment payload (rides the ordinary eth
frame — the payload checksum covers header + scales + data, so a corrupt
SCALE recovers exactly like a corrupt payload byte):

    magic  u8   (0xB5 — malformed-payload fail-fast, second line behind
                 the frame checksum)
    qcode  u8   (DTYPE_CODES code of the quantized payload dtype)
    block  u16  (elements per scale block)
    count  u32  (payload element count)
    scales f32[ceil(count/block)]
    data   qdtype[count]

The payload is SELF-DESCRIBING: the receiver dequantizes from the header
alone, so the block size is a per-sender runtime choice (tuner-
recommended) that never needs wire-level agreement — only the
BLOCK_SCALED compression flag in the call descriptor does, like every
other compression flag.

Quantization semantics (the numpy REFERENCE — ``native/
combine_kernels.c`` carries compiled twins held BIT-IDENTICAL by
tests/test_combine_native.py, so serial/streamed/native-vs-numpy
differentials all agree):

* per block: ``amax = max(|x|)`` (NaN-propagating), ``scale = amax /
  qmax`` clamped to 1.0 unless positive, normal and finite;
* quantize: ``q = cast(x * (1/scale))`` — fp8 casts follow ml_dtypes
  (round-to-nearest-even, e4m3fn overflows to NaN, e5m2 to inf); int8
  rounds half-to-even, clips to [-127, 127], and quantizes non-finite
  values to 0;
* dequantize: ``x' = float32(q) * scale`` — one f32 rounding;
* the fused combine step is ``func(other, dequant(q))`` with all
  arithmetic in f32 (widen-accumulate): per-hop error is bounded by one
  quantization of the travelling partial, never compounding through the
  accumulator.

Error model: for fp8-e4m3 the per-element dequantization error is at
most ``amax(block) * 2^-4 / (1 - 2^-4)`` (half-ulp at the block scale);
int8 bounds at ``amax/254``. A W-rank ring allreduce requantizes the
travelling partial W-2 times plus the phase-2 relay, so end-to-end
error is ≤ ``(W) * eps_q * max|partial|`` — linear in hops because
accumulation stays f32 (docs/ARCHITECTURE.md, "Quantized wire").
"""

from __future__ import annotations

import struct

import numpy as np

from .constants import ReduceFunc
from .tracing import METRICS

__all__ = [
    "MAGIC", "HDR_BYTES", "MIN_BLOCK", "MAX_BLOCK", "DEFAULT_BLOCK",
    "is_quantizable", "packed_nbytes", "seg_elems", "quantize_packed",
    "dequantize_packed", "dequant_combine_packed", "QuantFormatError",
]

MAGIC = 0xB5
_HDR = struct.Struct("<BBHI")       # magic, qcode, block, count
HDR_BYTES = _HDR.size               # 8
# Block-size envelope: segmentation reserves scale overhead for the
# SMALLEST legal block (4 bytes per 32 elements = 1/8 byte/elem), so the
# packed segment fits the rx buffer for ANY runtime block choice and the
# compiled-plan cache never keys on the block size.
MIN_BLOCK = 32
MAX_BLOCK = 4096
DEFAULT_BLOCK = 128

_FLT_MIN = np.float32(1.1754943508222875e-38)   # smallest normal f32

# quantizable wire dtypes -> (protocol code, qmax). Codes are
# emulator/protocol.py DTYPE_CODES values, listed literally like
# native_combine's table so importing this module never touches the
# emulator package (test_quantize pins them against protocol's).
_QCODES = {"int8": 6, "float8_e4m3fn": 8, "float8_e5m2": 9}
_QMAX = {"int8": 127.0, "float8_e4m3fn": 448.0, "float8_e5m2": 57344.0}

_NP_FUNCS = {
    ReduceFunc.SUM: np.add,
    ReduceFunc.MAX: np.maximum,
    ReduceFunc.MIN: np.minimum,
    ReduceFunc.PROD: np.multiply,
}


class QuantFormatError(ValueError):
    """A block-scaled payload failed structural validation (bad magic,
    dtype code, block, count or byte length). Normally unreachable —
    the frame checksum rejects corruption before decode — so this is
    the typed second line for checksum-off worlds."""


def is_quantizable(dtype) -> bool:
    """True when ``dtype`` can be a block-scaled wire dtype."""
    return np.dtype(dtype).name in _QCODES


def _qdtype_of(code: int) -> np.dtype:
    for name, c in _QCODES.items():
        if c == code:
            if name == "int8":
                return np.dtype(np.int8)
            import ml_dtypes
            return np.dtype(getattr(ml_dtypes, name))
    raise QuantFormatError(f"unknown quantized dtype code {code}")


def clamp_block(block: int) -> int:
    """Clamp a requested block size into the legal envelope, rounded
    DOWN to a power of two — the call descriptor carries the block as a
    log2 nibble (protocol.pack_call's qblock byte), so every tier
    reconstructs the identical value."""
    b = max(MIN_BLOCK, min(MAX_BLOCK, int(block)))
    return 1 << (b.bit_length() - 1)


def n_blocks(count: int, block: int) -> int:
    return -(-int(count) // int(block))


def packed_nbytes(count: int, block: int, qbytes: int = 1) -> int:
    """Exact wire bytes of one packed segment."""
    return HDR_BYTES + 4 * n_blocks(count, block) + count * qbytes


def seg_elems(max_segment_size: int, qbytes: int = 1) -> int:
    """Elements per wire segment for a block-scaled send, independent of
    the runtime block choice: reserves header + worst-case (MIN_BLOCK)
    scale overhead so ``packed_nbytes(n, block) <= max_segment_size``
    for every legal block. The twin of moveengine._seg_elems's
    compressed-elem division, kept here so the planner and the device
    cannot drift."""
    # 4 bytes of scale per MIN_BLOCK elems = 8*qbytes+1 eighth-bytes per
    # elem; 12 covers the header plus the final partial block's scale
    return max(1, 8 * (int(max_segment_size) - HDR_BYTES - 4)
               // (8 * int(qbytes) + 1))


# -- metrics (module counters + collector: per-segment registry incs are
#    the storm-shaped cost the daemon collectors avoid) ---------------------

_tx = [0, 0, 0]      # [segments, blocks, wire bytes saved]
_rx = [0, 0]         # [segments, blocks]
_calls = [0, 0]      # [native, numpy] codec calls


class _Collector:
    pass


_collector_owner = _Collector()


def _collector_rows(_owner):
    yield ("counter", "quant_segments_total", {"dir": "tx"}, _tx[0])
    yield ("counter", "quant_segments_total", {"dir": "rx"}, _rx[0])
    yield ("counter", "quant_blocks_total", {"dir": "tx"}, _tx[1])
    yield ("counter", "quant_blocks_total", {"dir": "rx"}, _rx[1])
    yield ("counter", "quant_wire_bytes_saved_total", {}, _tx[2])
    yield ("counter", "quant_codec_calls_total", {"path": "native"},
           _calls[0])
    yield ("counter", "quant_codec_calls_total", {"path": "numpy"},
           _calls[1])


METRICS.register_collector(_collector_owner, _collector_rows)


def counters() -> dict:
    """Snapshot for tests/benches: tx/rx segment+block counts and wire
    bytes saved so far in this process."""
    return {"tx_segments": _tx[0], "tx_blocks": _tx[1],
            "wire_bytes_saved": _tx[2], "rx_segments": _rx[0],
            "rx_blocks": _rx[1], "native_calls": _calls[0],
            "numpy_calls": _calls[1]}


# -- native dispatch --------------------------------------------------------

def _native():
    """The compiled codec module (native/combine_kernels.c) or None —
    resolved through native_combine's loader so both compiled lanes
    share one .so, one build path and one enable knob."""
    from . import native_combine
    lib = native_combine.module()
    # older prebuilt .so without the bs entries degrades to numpy
    if lib is not None and hasattr(lib, "bs_quantize"):
        return lib
    return None


# -- numpy reference --------------------------------------------------------

def _np_scales(x: np.ndarray, block: int, qmax: float) -> np.ndarray:
    n = x.size
    nb = n_blocks(n, block)
    a = np.abs(x)
    if n != nb * block:
        a = np.concatenate([a, np.zeros(nb * block - n, np.float32)])
    amax = a.reshape(nb, block).max(axis=1)
    with np.errstate(invalid="ignore", over="ignore"):
        s = (amax / np.float32(qmax)).astype(np.float32)
        good = (s >= _FLT_MIN) & (s < np.inf)
    return np.where(good, s, np.float32(1.0))


def _np_quantize(x: np.ndarray, qdtype: np.dtype, block: int
                 ) -> tuple[np.ndarray, np.ndarray]:
    """(scales f32[nb], q qdtype[n]) — the reference the compiled
    kernel is held bit-identical to."""
    s = _np_scales(x, block, _QMAX[qdtype.name])
    inv = np.float32(1.0) / s
    with np.errstate(invalid="ignore", over="ignore"):
        v = x * np.repeat(inv, block)[:x.size]
        if qdtype.name == "int8":
            q = np.where(np.isfinite(v),
                         np.clip(np.rint(v), -127, 127), 0).astype(np.int8)
        else:
            q = v.astype(qdtype)
    return s, q


def _np_dequant(scales: np.ndarray, q: np.ndarray, block: int
                ) -> np.ndarray:
    with np.errstate(invalid="ignore", over="ignore"):
        return (q.astype(np.float32)
                * np.repeat(scales, block)[:q.size]).astype(np.float32)


def ring_reduce_scatter_reference(chunks, func: ReduceFunc,
                                  qdtype: np.dtype, block: int) -> list:
    """Numpy reference of the device tier's block-scaled ring
    reduce-scatter (parallel.collectives.ring_reduce_scatter_bs_shard).
    ``chunks[r]``: rank r's (W, n) chunk view. Rank r starts by
    quantizing chunk (r+1)%W, receives from (r+1)%W each hop, and fuses
    func(local chunk, dequant) with fresh scales per hop. Returns
    out[r] = rank r's reduced chunk r."""
    W = len(chunks)
    npf = _NP_FUNCS[ReduceFunc(func)]
    state = {r: _np_quantize(chunks[r][(r + 1) % W], qdtype, block)
             for r in range(W)}
    out = {}
    for i in range(1, W):
        nxt = {}
        for r in range(W):
            s, q = state[(r + 1) % W]
            acc = npf(chunks[r][(r + 1 + i) % W], _np_dequant(s, q, block))
            if i < W - 1:
                nxt[r] = _np_quantize(acc, qdtype, block)
            else:
                out[r] = acc
        state = nxt
    return [out[r] for r in range(W)]


def ring_allgather_reference(mine, qdtype: np.dtype, block: int) -> list:
    """Numpy reference of the block-scaled ring allgather: own chunk
    exact, remote chunks carry exactly ONE quantization (relays forward
    bytes)."""
    W = len(mine)
    enc = [_np_quantize(mine[o], qdtype, block) for o in range(W)]
    return [np.concatenate([mine[o] if o == r
                            else _np_dequant(enc[o][0], enc[o][1], block)
                            for o in range(W)]) for r in range(W)]


def ring_allreduce_reference(ins, func: ReduceFunc, qdtype: np.dtype,
                             block: int) -> list:
    """Numpy reference of the block-scaled ring allreduce: the
    reduce-scatter then allgather above over zero-padded equal chunks."""
    W = len(ins)
    n = ins[0].size
    pad = (-n) % W
    chunks = [np.concatenate([x, np.zeros(pad, np.float32)]).reshape(W, -1)
              for x in ins]
    mine = ring_reduce_scatter_reference(chunks, func, qdtype, block)
    return [o[:n] for o in ring_allgather_reference(mine, qdtype, block)]


# -- packed codec (the executor's entry points) -----------------------------

def quantize_packed(x: np.ndarray, qdtype, block: int) -> np.ndarray:
    """Pack one segment: f32 operand -> owned uint8 array
    [header | scales | payload]. ``x`` must be 1-D contiguous float32
    (the executor's combine-result shape)."""
    qdtype = np.dtype(qdtype)
    code = _QCODES[qdtype.name]
    block = clamp_block(block)
    n = int(x.size)
    nb = n_blocks(n, block)
    out = np.empty(HDR_BYTES + 4 * nb + n, np.uint8)
    _HDR.pack_into(out, 0, MAGIC, code, block, n)
    scales = out[HDR_BYTES:HDR_BYTES + 4 * nb].view(np.float32)
    qview = out[HDR_BYTES + 4 * nb:]
    lib = _native()
    if lib is not None and x.flags.c_contiguous:
        lib.bs_quantize(code, block, x, scales, qview)
        _calls[0] += 1
    else:
        s, q = _np_quantize(np.ascontiguousarray(x, np.float32), qdtype,
                            block)
        scales[:] = s
        qview[:] = q.view(np.uint8)
        _calls[1] += 1
    _tx[0] += 1
    _tx[1] += nb
    _tx[2] += max(0, n * 4 - out.nbytes)
    return out


def _parse(payload, expect_count: int):
    """Validate + split one packed segment -> (qcode, block, scales
    bytes-view, q bytes-view, n, nb)."""
    mv = memoryview(payload)
    if mv.ndim != 1 or mv.itemsize != 1:
        mv = mv.cast("B")
    if len(mv) < HDR_BYTES:
        raise QuantFormatError(
            f"block-scaled payload shorter than its header "
            f"({len(mv)} B)")
    magic, code, block, n = _HDR.unpack_from(mv, 0)
    if magic != MAGIC:
        raise QuantFormatError(
            f"bad block-scaled magic {magic:#x} (want {MAGIC:#x})")
    if not MIN_BLOCK <= block <= MAX_BLOCK:
        raise QuantFormatError(f"illegal block size {block}")
    if expect_count is not None and n != expect_count:
        raise QuantFormatError(
            f"payload carries {n} elements, move expects {expect_count}")
    qdtype = _qdtype_of(code)
    nb = n_blocks(n, block)
    want = HDR_BYTES + 4 * nb + n * qdtype.itemsize
    if len(mv) != want:
        raise QuantFormatError(
            f"payload is {len(mv)} B, layout wants {want} B")
    return (code, qdtype, block,
            mv[HDR_BYTES:HDR_BYTES + 4 * nb],
            mv[HDR_BYTES + 4 * nb:want], n, nb)


def dequantize_packed(payload, expect_count: int | None = None
                      ) -> np.ndarray:
    """Unpack one segment to a fresh f32 array."""
    code, qdtype, block, smv, qmv, n, nb = _parse(payload, expect_count)
    out = np.empty(n, np.float32)
    lib = _native()
    if lib is not None:
        lib.bs_dequant(code, block, smv, qmv, out)
        _calls[0] += 1
    else:
        out[:] = _np_dequant(np.frombuffer(smv, np.float32),
                             np.frombuffer(qmv, qdtype), block)
        _calls[1] += 1
    _rx[0] += 1
    _rx[1] += nb
    return out


def dequant_combine_packed(payload, other: np.ndarray, func: ReduceFunc,
                           out: np.ndarray | None = None,
                           expect_count: int | None = None) -> np.ndarray:
    """The fused dequant -> accumulate step: ``out = func(other,
    dequant(payload))`` with f32 accumulation, one compiled pass when
    the native codec is available (GIL released at segment sizes).
    ``other`` must be f32; ``out`` may alias neither input's memory in
    the numpy fallback sense (the executor passes arena scratch)."""
    code, qdtype, block, smv, qmv, n, nb = _parse(payload, expect_count)
    if other.size != n:
        raise QuantFormatError(
            f"combine operand has {other.size} elements, payload {n}")
    lib = _native()
    if (lib is not None and other.dtype == np.float32
            and other.flags.c_contiguous):
        if out is None:
            out = np.empty(n, np.float32)
        lib.bs_combine(int(func), code, block, smv, qmv, other, out)
        _calls[0] += 1
    else:
        v = _np_dequant(np.frombuffer(smv, np.float32),
                        np.frombuffer(qmv, qdtype), block)
        npf = _NP_FUNCS[ReduceFunc(func)]
        if out is None:
            out = npf(other.astype(np.float32, copy=False), v)
        else:
            npf(other.astype(np.float32, copy=False), v, out=out)
        _calls[1] += 1
    _rx[0] += 1
    _rx[1] += nb
    return out
