"""Collective algorithms over a jax mesh axis: the TPU dataplane.

Two algorithm families per collective, mirroring the reference's
sw/hw × ring/round-robin selectors (driver/xrt/include/xlnx-consts.hpp:43-66):

* ``xla`` — the fused path: one XLA collective op (psum / all_gather /
  psum_scatter / all_to_all). XLA lowers these onto ICI with its own
  ring/tree schedules; this is the peak-bandwidth path.
* ``ring`` — the decomposed path: explicit ``lax.ppermute`` rings with the
  same chunk schedule as the firmware's ring collectives
  (ccl_offload_control.c:632-1098): decreasing-rank flow, rank r starts by
  sending chunk r+1, round i handles chunk r+1+i, ending with its own chunk.
  This path supports wire compression per hop and is the substrate for
  fused computation/communication (ring attention, pipelined kernels).

All ``*_shard`` functions run INSIDE shard_map (per-shard views); the
:class:`MeshCollectives` wrapper builds/jits the shard_map programs for
global arrays sharded over the axis.

Wire compression (reference: fp32↔fp16 clane plugins + ETH_COMPRESSED):
``wire_dtype`` casts each hop's payload before the ppermute and upcasts
after, accumulating in the uncompressed dtype.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..constants import ReduceFunc
from ..ops.compression import (BS_WIRE_DTYPE_NAMES, FP8_DTYPE_NAMES,
                               _bs_scalars, bs_combine_requant,
                               bs_dequant_combine, bs_dequantize,
                               bs_quantize, fp8_dequantize, fp8_quantize)

_REDUCE_OPS: dict[ReduceFunc, Callable] = {
    ReduceFunc.SUM: jnp.add,
    ReduceFunc.MAX: jnp.maximum,
    ReduceFunc.MIN: jnp.minimum,
    ReduceFunc.PROD: jnp.multiply,
}

_PSUM_LIKE = {
    ReduceFunc.SUM: lax.psum,
    ReduceFunc.MAX: lax.pmax,
    ReduceFunc.MIN: lax.pmin,
}


def mark_varying(x: jnp.ndarray, axis_names) -> jnp.ndarray:
    """Mark ``x`` as varying over mesh ``axis_names`` for check_vma.

    Fresh constants (and psum-like outputs) are axis-invariant inside
    shard_map; feeding one as a loop carry whose body output varies makes
    the scan carry types mismatch.  One shim for the JAX API drift:
    pcast (current) -> pvary (older) -> no-op (oldest, no vma tracking)."""
    if isinstance(axis_names, str):
        axis_names = (axis_names,)
    if hasattr(lax, "pcast"):
        return lax.pcast(x, tuple(axis_names), to="varying")
    if hasattr(lax, "pvary"):  # older jax
        return lax.pvary(x, tuple(axis_names))
    return x  # oldest jax: no varying-axes tracking, nothing to align


def axis_reduce(x: jnp.ndarray, axis_name: str,
                func: ReduceFunc) -> jnp.ndarray:
    """Reduce ``x`` elementwise across ``axis_name`` for any ReduceFunc.

    SUM/MAX/MIN lower to the fused XLA collective; PROD (which has no XLA
    collective) falls back to all_gather + local reduce."""
    fused = _PSUM_LIKE.get(func)
    if fused is not None:
        return fused(x, axis_name)
    gathered = lax.all_gather(x, axis_name)
    return jnp.prod(gathered, axis=0)


def _ring_perm(W: int) -> list[tuple[int, int]]:
    """Decreasing-rank flow ring: rank i sends to i-1 (firmware flow)."""
    return [(i, (i - 1) % W) for i in range(W)]


def _hop(x: jnp.ndarray, axis_name: str, perm, wire_dtype) -> jnp.ndarray:
    """One ring hop, optionally compressed on the wire.

    fp16/bf16 wire dtypes are straight casts (the reference's fp32<->fp16
    clane); fp8 dtypes use the shared scaled codec (per-hop absmax scale
    travels with the payload — the EQuARX-style quantized-collective
    extension, ops/compression.fp8_quantize)."""
    if wire_dtype is None or x.dtype == jnp.dtype(wire_dtype):
        return lax.ppermute(x, axis_name, perm)
    if jnp.dtype(wire_dtype).name in FP8_DTYPE_NAMES:
        q, scale = fp8_quantize(x, wire_dtype)
        q = lax.ppermute(q, axis_name, perm)
        scale = lax.ppermute(scale, axis_name, perm)
        return fp8_dequantize(q, scale, x.dtype)
    return lax.ppermute(x.astype(wire_dtype), axis_name, perm).astype(x.dtype)


# ---------------------------------------------------------------------------
# In-shard_map ring algorithms (per-shard views)
# ---------------------------------------------------------------------------

def ring_reduce_scatter_shard(x: jnp.ndarray, axis_name: str,
                              func: ReduceFunc = ReduceFunc.SUM,
                              wire_dtype=None) -> jnp.ndarray:
    """Ring reduce-scatter. ``x``: (W, chunk...) per shard — every rank holds
    W chunks; returns this rank's fully-reduced chunk (chunk...,).

    Chunk schedule parity: firmware reduce_scatter (c:860-939) — send chunk
    me+1, round i reduces+forwards chunk me+1+i, final round keeps chunk me.
    """
    W = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    op = _REDUCE_OPS[func]
    perm = _ring_perm(W)

    def chunk(i):
        return lax.dynamic_index_in_dim(x, (me + 1 + i) % W, keepdims=False)

    def body(i, acc):
        acc = _hop(acc, axis_name, perm, wire_dtype)
        return op(acc, chunk(i))

    return lax.fori_loop(1, W, body, chunk(0), unroll=True)


def ring_allgather_shard(x: jnp.ndarray, axis_name: str,
                         wire_dtype=None) -> jnp.ndarray:
    """Ring allgather. ``x``: (chunk...,) per shard; returns (W, chunk...).

    Parity: firmware allgather (c:727-828) — send own chunk along the ring;
    chunk me+i arrives at round i (decreasing-rank flow).
    """
    W = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    perm = _ring_perm(W)
    out = jnp.zeros((W,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, me, 0)

    def body(i, carry):
        out, buf = carry
        buf = _hop(buf, axis_name, perm, wire_dtype)
        out = lax.dynamic_update_index_in_dim(out, buf, (me + i) % W, 0)
        return out, buf

    out, _ = lax.fori_loop(1, W, body, (out, x), unroll=True)
    return out


def ring_allreduce_shard(x: jnp.ndarray, axis_name: str,
                         func: ReduceFunc = ReduceFunc.SUM,
                         wire_dtype=None) -> jnp.ndarray:
    """Ring allreduce = ring reduce-scatter + ring allgather over W chunks
    of the flattened shard (firmware allreduce, c:942-1098). ``x``: any
    shape, same on all ranks; returns the elementwise reduction."""
    W = jax.lax.axis_size(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.size) % W
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(W, -1)
    mine = ring_reduce_scatter_shard(chunks, axis_name, func, wire_dtype)
    full = ring_allgather_shard(mine, axis_name, wire_dtype)
    out = full.reshape(-1)
    if pad:
        out = out[:flat.size - pad]
    return out.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Device-tier block-scaled quantized rings (Pallas fused codec per hop)
# ---------------------------------------------------------------------------
# Same chunk schedules as the plain rings above, but each hop's payload
# travels as (wire-dtype codes, per-block f32 scales) and the receive
# side runs the fused dequant -> f32-accumulate -> requant Pallas kernel
# (ops/compression.bs_combine_requant): the f32 partial never exists as
# a wire buffer. Every reduce-scatter hop requantizes against FRESH
# scales, so per-hop error stays bounded and never compounds (the PR 15
# quantized-wire contract); the allgather relays forward the SAME
# (q, scales) bytes unchanged — a single quantization, bit-stable
# through any number of relays (the bcast idempotence convention).
#
# ``scalars`` is the eager (one, qmax) pair from compression._bs_scalars
# threaded through as program arguments — see its docstring for why
# building it inside a trace breaks bit-identity with quant.py.

def ring_reduce_scatter_bs_shard(x: jnp.ndarray, axis_name: str,
                                 func: ReduceFunc, wire_dtype,
                                 qblock: int, scalars=None) -> jnp.ndarray:
    """Block-scaled ring reduce-scatter. ``x``: (W, chunk...) per shard;
    returns this rank's reduced chunk in f32 accumulation semantics,
    cast back to ``x.dtype``."""
    W = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    perm = _ring_perm(W)

    def chunk(i):
        c = lax.dynamic_index_in_dim(x, (me + 1 + i) % W, keepdims=False)
        return c.astype(jnp.float32)

    if W == 1:
        return chunk(0).astype(x.dtype)
    q, s = bs_quantize(chunk(0), wire_dtype, qblock, scalars)
    out = None
    for i in range(1, W):           # python-unrolled: (q, s) carry
        q = lax.ppermute(q, axis_name, perm)
        s = lax.ppermute(s, axis_name, perm)
        if i < W - 1:
            q, s = bs_combine_requant(q, s, chunk(i), func, wire_dtype,
                                      qblock, scalars)
        else:                       # round-closing hop: no requant
            out = bs_dequant_combine(q, s, chunk(i), func, qblock,
                                     scalars)
    return out.astype(x.dtype)


def ring_allgather_bs_shard(x: jnp.ndarray, axis_name: str, wire_dtype,
                            qblock: int, scalars=None) -> jnp.ndarray:
    """Block-scaled ring allgather. ``x``: (chunk...,) per shard; returns
    (W, chunk...). The own chunk lands exact; remote chunks carry one
    quantization regardless of relay distance (bytes forwarded as-is)."""
    W = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    perm = _ring_perm(W)
    out = jnp.zeros((W,) + x.shape, x.dtype)
    out = lax.dynamic_update_index_in_dim(out, x, me, 0)
    if W == 1:
        return out
    q, s = bs_quantize(x.astype(jnp.float32), wire_dtype, qblock, scalars)
    for i in range(1, W):
        q = lax.ppermute(q, axis_name, perm)
        s = lax.ppermute(s, axis_name, perm)
        landed = bs_dequantize(q, s, qblock).astype(x.dtype)
        out = lax.dynamic_update_index_in_dim(out, landed, (me + i) % W, 0)
    return out


def ring_allreduce_bs_shard(x: jnp.ndarray, axis_name: str,
                            func: ReduceFunc, wire_dtype,
                            qblock: int, scalars=None) -> jnp.ndarray:
    """Block-scaled ring allreduce = quantized reduce-scatter + quantized
    allgather over W chunks of the flattened shard (the EQuARX-style
    fused quantized collective)."""
    W = jax.lax.axis_size(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.size) % W
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(W, -1)
    mine = ring_reduce_scatter_bs_shard(chunks, axis_name, func,
                                        wire_dtype, qblock, scalars)
    full = ring_allgather_bs_shard(mine, axis_name, wire_dtype, qblock,
                                   scalars)
    out = full.reshape(-1)
    if pad:
        out = out[:flat.size - pad]
    return out.reshape(shape).astype(dtype)


def ring_allreduce(x, axis_name: str, func: ReduceFunc = ReduceFunc.SUM,
                   wire_dtype=None):
    """Alias usable directly inside shard_map/pjit programs."""
    return ring_allreduce_shard(x, axis_name, func, wire_dtype)


def ring_allgather(x, axis_name: str, wire_dtype=None):
    return ring_allgather_shard(x, axis_name, wire_dtype)


def ring_reduce_scatter(x, axis_name: str, func: ReduceFunc = ReduceFunc.SUM,
                        wire_dtype=None):
    return ring_reduce_scatter_shard(x, axis_name, func, wire_dtype)


def multi_axis_ring_allreduce_shard(x: jnp.ndarray,
                                    axis_names: tuple[str, ...],
                                    func: ReduceFunc = ReduceFunc.SUM,
                                    wire_dtype=None) -> jnp.ndarray:
    """Allreduce over an N-D torus that drives EVERY mesh axis's links
    simultaneously — the schedule the ICI roofline's full-line-rate
    claim assumes (docs/ROOFLINE.md assumption 2; scaling-book multi-ring
    recipe).

    The payload splits into len(axes) parts; part i runs a hierarchical
    reduce-scatter down the axes in rotation order starting at axis i,
    then all-gathers back up. Each part's HEAVY first phase therefore
    rides a different physical axis, and the parts' chains are
    independent inside one program, so the compiler can overlap them:
    aggregate injection bandwidth = all axes at once, not one ring.

    ``x``: (n,) per shard, n divisible by prod(axis sizes) * len(axes)
    for clean splits (pad outside). Returns the fully-reduced (n,)."""
    k = len(axis_names)
    parts = jnp.split(x, k)
    outs = []
    for i, part in enumerate(parts):
        order = axis_names[i:] + axis_names[:i]
        y = part
        # reduce-scatter cascade: each axis scatters its factor of the
        # shard, so phase j moves a 1/prod(earlier sizes) fraction of
        # the part on axis order[j] — the first (biggest) phase is axis i
        for ax in order:
            W = jax.lax.axis_size(ax)
            y = ring_reduce_scatter_shard(y.reshape(W, -1), ax, func,
                                          wire_dtype)
        # allgather cascade back up in reverse
        for ax in reversed(order):
            y = ring_allgather_shard(y, ax, wire_dtype).reshape(-1)
        outs.append(y)
    return jnp.concatenate(outs)


def masked_bcast(x: jnp.ndarray, root, axis_name: str) -> jnp.ndarray:
    """Broadcast via masked reduction — XLA lowers this to its tree/ring
    broadcast schedule. Works for any dtype (uses where+psum)."""
    me = lax.axis_index(axis_name)
    contrib = jnp.where(me == root, x, jnp.zeros_like(x))
    if jnp.issubdtype(x.dtype, jnp.integer):
        return lax.psum(contrib, axis_name)
    return lax.psum(contrib, axis_name).astype(x.dtype)


def send_recv(x: jnp.ndarray, pairs: list[tuple[int, int]],
              axis_name: str) -> jnp.ndarray:
    """Point-to-point transfer: ppermute over explicit (src, dst) pairs.
    Ranks not named as a destination receive zeros (they ignore the
    result). This is the SPMD substrate for tag-matched send/recv — the
    host-side rendezvous pairs the calls (device/tpu.py)."""
    return lax.ppermute(x, axis_name, pairs)


def alltoall_shard(x: jnp.ndarray, axis_name: str,
                   wire_dtype=None) -> jnp.ndarray:
    """x: (W, chunk...) per shard -> (W, chunk...) transposed across ranks.

    With a wire dtype, chunks cast BEFORE transit (the exchange itself
    moves wire-width bytes) and upcast on arrival; the rank's own chunk
    lands from itself and is restored exact (the emulator tier's
    wire_q_except contract: only data that actually crossed the wire is
    quantized)."""
    if wire_dtype is None or x.dtype == jnp.dtype(wire_dtype):
        return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)
    out_q = lax.all_to_all(x.astype(wire_dtype), axis_name, split_axis=0,
                           concat_axis=0, tiled=False).astype(x.dtype)
    me = lax.axis_index(axis_name)
    keep = lax.broadcasted_iota(jnp.int32,
                                (x.shape[0],) + (1,) * (x.ndim - 1), 0) == me
    # row me of the exchange output is this rank's own x[me] round-tripped
    # through the wire; substitute the exact original
    return jnp.where(keep, x, out_q)


_AXIS_REDUCERS = {ReduceFunc.SUM: jnp.sum, ReduceFunc.MAX: jnp.max,
                  ReduceFunc.MIN: jnp.min, ReduceFunc.PROD: jnp.prod}


def xla_compressed_reduce_scatter_shard(chunks: jnp.ndarray, axis_name: str,
                                        func: ReduceFunc,
                                        wire_dtype) -> jnp.ndarray:
    """Reduce-scatter with a compressed wire but UNCOMPRESSED accumulation
    on the fused-XLA path: all_to_all moves the compressed chunks (pure
    data movement, no arithmetic), then the W contributions are upcast and
    reduced locally. This is the XLA analog of the reference's
    decompress-before-arith clane routing (dma_mover.cpp:44-168) and
    matches the ring path's numerics (``_hop`` upcasts before reducing) —
    a plain ``psum(x.astype(wire))`` would instead accumulate W-1 rounding
    errors in the wire dtype.

    ``chunks``: (W, chunk...) per shard; returns this rank's reduced chunk.
    fp8 wires carry a per-(rank, chunk) absmax scale alongside the payload
    (EQuARX-style), like the ring-hop codec."""
    dtype = chunks.dtype
    if jnp.dtype(wire_dtype).name in FP8_DTYPE_NAMES:
        tail = tuple(range(1, chunks.ndim))
        q, scale = fp8_quantize(chunks, wire_dtype, axes=tail)  # (W,) scales
        q = alltoall_shard(q, axis_name)
        scale = lax.all_to_all(scale, axis_name, 0, 0)
        up = fp8_dequantize(q, scale)
        return _AXIS_REDUCERS[func](up, axis=0).astype(dtype)
    recv = alltoall_shard(chunks.astype(wire_dtype), axis_name)
    return _AXIS_REDUCERS[func](recv.astype(dtype), axis=0)


def xla_compressed_allgather_shard(x: jnp.ndarray, axis_name: str,
                                   wire_dtype) -> jnp.ndarray:
    """All-gather with a compressed wire: a straight cast each way — no
    arithmetic happens in the wire dtype. fp8 wires gather a per-rank
    scale next to the payload."""
    if jnp.dtype(wire_dtype).name in FP8_DTYPE_NAMES:
        q, scale = fp8_quantize(x, wire_dtype)
        q = lax.all_gather(q, axis_name)
        s = lax.all_gather(scale, axis_name)
        return fp8_dequantize(q, s, x.dtype)
    return lax.all_gather(x.astype(wire_dtype), axis_name).astype(x.dtype)


def xla_compressed_allreduce_shard(x: jnp.ndarray, axis_name: str,
                                   func: ReduceFunc,
                                   wire_dtype) -> jnp.ndarray:
    """Fused-path allreduce with compressed wire + uncompressed
    accumulation: compressed reduce-scatter (all_to_all + local upcast
    reduce) then compressed all-gather — the firmware's fused 2-phase
    structure (c:942-1098) lowered to XLA's fused collectives."""
    W = jax.lax.axis_size(axis_name)
    shape, dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    pad = (-flat.size) % W
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(W, -1)
    mine = xla_compressed_reduce_scatter_shard(chunks, axis_name, func,
                                               wire_dtype)
    full = xla_compressed_allgather_shard(mine, axis_name, wire_dtype)
    out = full.reshape(-1)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(dtype)


# ---------------------------------------------------------------------------
# Global-array wrappers: build + cache shard_map programs over a mesh
# ---------------------------------------------------------------------------

class MeshCollectives:
    """Collectives over global jax.Arrays sharded on ``axis_name`` of a mesh.

    Global layout convention (SPMD controller view): operands carry a
    leading ``W`` axis — element [r] is rank r's operand — sharded over the
    mesh axis. This is the TPU-backend currency the ACCL driver uses.

    Programs are jitted and cached per (op, algorithm, shapes, dtypes).
    """

    def __init__(self, mesh: Mesh, axis_name: str = "rank"):
        self.mesh = mesh
        self.axis_name = axis_name
        self.W = mesh.shape[axis_name]
        # per-instance program cache (an lru_cache on methods would pin the
        # instance and its jitted executables in a process-global cache)
        self._cache: dict[tuple, Callable] = {}
        # hot-path constants: device list in axis order + the flat 1-D
        # sharding (rebuilding either per call costs ~100us of pure
        # Python on the device-resident driver path)
        import numpy as _np
        self.device_list = list(_np.asarray(mesh.devices).reshape(-1))
        self.flat_sharding = NamedSharding(mesh, P(axis_name))

    # specs: leading axis is the per-rank axis
    def _sharded(self, extra_dims: int = 0) -> P:
        return P(self.axis_name, *([None] * extra_dims))

    def shard(self, per_rank_values) -> jax.Array:
        """Stack host per-rank values [W, ...] and shard over the axis."""
        import numpy as np
        stacked = np.stack(per_rank_values)
        sharding = NamedSharding(self.mesh, self._sharded(stacked.ndim - 1))
        return jax.device_put(stacked, sharding)

    @staticmethod
    def _bs_eligible(op: str, wire: str | None, qblock: int) -> bool:
        """The fused block-scaled ring lane exists for the ring-shaped
        reduction collectives and the quantizable wire dtypes only."""
        return bool(qblock) and wire in BS_WIRE_DTYPE_NAMES and op in (
            "allreduce", "reduce_scatter", "allgather")

    def _bs_shard_fn(self, op: str, func: ReduceFunc, wire: str,
                     qblock: int) -> Callable:
        """Per-shard body for the block-scaled quantized rings:
        f(x, one, qmax) with x (1, n) and the eager runtime scalars
        threaded through as replicated program arguments."""
        ax = self.axis_name
        wdt = jnp.dtype(wire)
        if op == "allreduce":
            def f(x, one, qmax):
                return ring_allreduce_bs_shard(
                    x[0], ax, func, wdt, qblock, (one, qmax))[None]
        elif op == "reduce_scatter":
            def f(x, one, qmax):
                chunks = x[0].reshape(self.W, -1)
                return ring_reduce_scatter_bs_shard(
                    chunks, ax, func, wdt, qblock, (one, qmax))[None]
        else:  # allgather
            def f(x, one, qmax):
                return ring_allgather_bs_shard(
                    x[0], ax, wdt, qblock, (one, qmax)).reshape(-1)[None]
        return f

    @staticmethod
    def _resolved(op: str, algorithm: str, func: ReduceFunc) -> str:
        """The algorithm a plain (not block-scaled) program runs: XLA has
        no fused product-reduce collective, so such a reduction takes the
        ring path."""
        if func not in _PSUM_LIKE and algorithm == "xla" and op in (
                "allreduce", "reduce", "reduce_scatter"):
            return "ring"
        return algorithm

    def _shard_fn(self, op: str, algorithm: str, func: ReduceFunc,
                  wire: str | None, root: int | None) -> Callable:
        """Build the per-shard body f: (1, n_in) -> (1, n_out) shared by
        the stacked (W, n) and flat (W*n,) program layouts."""
        ax = self.axis_name
        wire_dtype = jnp.dtype(wire) if wire else None
        algorithm = self._resolved(op, algorithm, func)
        if op == "reduce" and algorithm == "ring":
            def f(x):
                r = ring_allreduce_shard(x[0], ax, func, wire_dtype)
                me = lax.axis_index(ax)
                return jnp.where(me == root, r, jnp.zeros_like(x[0]))[None]
            return f

        if op == "allreduce":
            if algorithm == "ring":
                def f(x):  # x per-shard: (1, n)
                    return ring_allreduce_shard(x[0], ax, func,
                                                wire_dtype)[None]
            elif wire_dtype is not None:
                # compressed wire, uncompressed accumulation (the clane
                # semantics) — NOT psum in the wire dtype
                def f(x):
                    return xla_compressed_allreduce_shard(
                        x[0], ax, func, wire_dtype)[None]
            else:
                def f(x):
                    return _PSUM_LIKE[func](x[0], ax).astype(x.dtype)[None]
        elif op == "reduce_scatter":
            # x: (W, W*chunk) global; out: (W, chunk)
            if algorithm == "ring":
                def f(x):
                    chunks = x[0].reshape(self.W, -1)
                    return ring_reduce_scatter_shard(chunks, ax, func,
                                                     wire_dtype)[None]
            elif wire_dtype is not None:
                def f(x):
                    chunks = x[0].reshape(self.W, -1)
                    return xla_compressed_reduce_scatter_shard(
                        chunks, ax, func, wire_dtype)[None]
            else:
                def f(x):
                    r = lax.psum_scatter(x[0].reshape(self.W, -1), ax,
                                         scatter_dimension=0, tiled=False)
                    return r.astype(x.dtype)[None]
        elif op == "allgather":
            # x: (W, chunk) global; out: (W, W*chunk)
            if algorithm == "ring":
                def f(x):
                    return ring_allgather_shard(x[0], ax,
                                                wire_dtype).reshape(-1)[None]
            elif wire_dtype is not None:
                def f(x):
                    return xla_compressed_allgather_shard(
                        x[0], ax, wire_dtype).reshape(-1)[None]
            else:
                def f(x):
                    return lax.all_gather(x[0], ax).reshape(-1)[None]
        elif op == "bcast":
            # binomial ppermute rounds: (W-1)|x| wire bytes; masked_bcast
            # (psum-over-mask) costs a full allreduce (VERDICT r3 weak-3).
            # The wire dtype rides INSIDE the program (cast per hop, cast
            # back at the receiver — idempotent, so multi-hop relays match
            # the emulator tier's single quantization bitwise)
            from .tree import binomial_bcast_shard

            def f(x):
                return binomial_bcast_shard(x[0], root, ax,
                                            wire_dtype)[None]
        elif op == "reduce":
            def f(x):
                if wire_dtype is not None:
                    # decompress-before-arith, like the allreduce path
                    r = xla_compressed_allreduce_shard(x[0], ax, func,
                                                       wire_dtype)
                else:
                    r = _PSUM_LIKE[func](x[0], ax).astype(x.dtype)
                me = lax.axis_index(ax)
                return jnp.where(me == root, r,
                                 jnp.zeros_like(x[0]))[None]
        elif op == "scatter":
            # binomial halving tree: O(W log W / 2) chunks on the wire;
            # the old masked psum_scatter paid reduce-scatter-class
            # W(W-1) chunks regardless of root
            from .tree import binomial_scatter_shard

            def f(x):
                chunks = x[0].reshape(self.W, -1)
                return binomial_scatter_shard(chunks, root, ax,
                                              wire_dtype)[None]
        elif op == "gather":
            # binomial doubling tree: O(W log W / 2) chunks on the wire;
            # all_gather+mask delivered W chunks to every rank, W(W-1)
            # total, to keep one copy
            from .tree import binomial_gather_shard

            def f(x):
                g = binomial_gather_shard(x[0], root, ax,
                                          wire_dtype).reshape(-1)
                return g[None]
        elif op == "alltoall":
            def f(x):
                chunks = x[0].reshape(self.W, -1)
                return alltoall_shard(chunks, ax,
                                      wire_dtype).reshape(-1)[None]
        else:
            raise NotImplementedError(op)
        return f

    def _bs_wrap(self, fn: Callable, wire: str) -> Callable:
        """Jit a block-scaled program and close over its eager runtime
        scalars: the returned callable keeps the plain prog(x) signature
        while (one, qmax) enter the XLA computation as real arguments —
        the only placement that survives constant folding bit-exactly
        (compression._bs_scalars)."""
        one, qmax = _bs_scalars(wire)
        raw = jax.jit(fn)

        def prog(x):
            return raw(x, one, qmax)

        return prog

    def _program(self, op: str, algorithm: str, func: ReduceFunc,
                 wire: str | None, root: int | None, qblock: int = 0):
        """Stacked layout: global (W, n) arrays, leading axis = rank."""
        ck = (op, algorithm, func, wire, root, qblock)
        cached = self._cache.get(ck)
        if cached is not None:
            return cached
        ax = self.axis_name
        if self._bs_eligible(op, wire, qblock):
            # check_vma off: shard_map has no replication rule for
            # pallas_call; every bs program output is rank-varying anyway
            f = _named(self._bs_shard_fn(op, func, wire, qblock),
                       op, "ring", wire, "bs")
            fn = jax.shard_map(f, mesh=self.mesh,
                               in_specs=(P(ax, None), P(None, None),
                                         P(None, None)),
                               out_specs=P(ax, None), check_vma=False)
            prog = self._cache[ck] = self._bs_wrap(fn, wire)
            return prog
        f = _named(self._shard_fn(op, algorithm, func, wire, root),
                   op, self._resolved(op, algorithm, func), wire)
        fn = jax.shard_map(f, mesh=self.mesh, in_specs=P(ax, None),
                           out_specs=P(ax, None))
        prog = self._cache[ck] = jax.jit(fn)
        return prog

    def _program_flat(self, op: str, algorithm: str, func: ReduceFunc,
                      wire: str | None, root: int | None, qblock: int = 0):
        """Flat layout: global (W*n,) arrays whose per-device shards are
        rank-local 1-D operands. This is the device-resident buffer path:
        shards assembled with jax.make_array_from_single_device_arrays
        keep their (n,) shape, so no per-shard host reshape is needed on
        either side of the call (the [None]/[0] axis plumbing is free
        inside the jitted program)."""
        ck = ("flat", op, algorithm, func, wire, root, qblock)
        cached = self._cache.get(ck)
        if cached is not None:
            return cached
        ax = self.axis_name
        if self._bs_eligible(op, wire, qblock):
            f = self._bs_shard_fn(op, func, wire, qblock)

            def g(x, one, qmax):
                return f(x[None], one, qmax)[0]

            fn = jax.shard_map(_named(g, op, "ring", wire, "bs"),
                               mesh=self.mesh,
                               in_specs=(P(ax), P(None, None), P(None, None)),
                               out_specs=P(ax), check_vma=False)
            prog = self._cache[ck] = self._bs_wrap(fn, wire)
            return prog
        f = self._shard_fn(op, algorithm, func, wire, root)

        def g(x):
            return f(x[None])[0]

        fn = jax.shard_map(_named(g, op, self._resolved(op, algorithm, func),
                                  wire),
                           mesh=self.mesh, in_specs=P(ax), out_specs=P(ax))
        prog = self._cache[ck] = jax.jit(fn)
        return prog

    # -- public ops (global arrays, leading W axis) ------------------------
    # qblock > 0 with a quantizable wire dtype selects the fused
    # block-scaled Pallas ring (device tier of the quantized wire);
    # qblock == 0 keeps the per-tensor compression paths.
    def allreduce(self, x: jax.Array, func: ReduceFunc = ReduceFunc.SUM,
                  algorithm: str = "xla", wire_dtype=None,
                  qblock: int = 0) -> jax.Array:
        return self._program("allreduce", algorithm, func,
                             _wire_name(wire_dtype), None, qblock)(x)

    def reduce_scatter(self, x: jax.Array,
                       func: ReduceFunc = ReduceFunc.SUM,
                       algorithm: str = "xla", wire_dtype=None,
                       qblock: int = 0) -> jax.Array:
        return self._program("reduce_scatter", algorithm, func,
                             _wire_name(wire_dtype), None, qblock)(x)

    def allgather(self, x: jax.Array, algorithm: str = "xla",
                  wire_dtype=None, qblock: int = 0) -> jax.Array:
        return self._program("allgather", algorithm, ReduceFunc.SUM,
                             _wire_name(wire_dtype), None, qblock)(x)

    def bcast(self, x: jax.Array, root: int = 0,
              wire_dtype=None) -> jax.Array:
        return self._program("bcast", "xla", ReduceFunc.SUM,
                             _wire_name(wire_dtype), root)(x)

    def reduce(self, x: jax.Array, root: int = 0,
               func: ReduceFunc = ReduceFunc.SUM, wire_dtype=None
               ) -> jax.Array:
        return self._program("reduce", "xla", func,
                             _wire_name(wire_dtype), root)(x)

    def scatter(self, x: jax.Array, root: int = 0,
                wire_dtype=None) -> jax.Array:
        return self._program("scatter", "xla", ReduceFunc.SUM,
                             _wire_name(wire_dtype), root)(x)

    def gather(self, x: jax.Array, root: int = 0,
               wire_dtype=None) -> jax.Array:
        return self._program("gather", "xla", ReduceFunc.SUM,
                             _wire_name(wire_dtype), root)(x)

    def alltoall(self, x: jax.Array, wire_dtype=None) -> jax.Array:
        return self._program("alltoall", "xla", ReduceFunc.SUM,
                             _wire_name(wire_dtype), None)(x)

    def _sendrecv_program(self, pairs: tuple[tuple[int, int], ...]):
        ck = ("exchange", pairs)
        cached = self._cache.get(ck)
        if cached is not None:
            return cached
        ax = self.axis_name

        def f(x):
            return send_recv(x[0], list(pairs), ax)[None]

        fn = jax.shard_map(_named(f, "exchange"), mesh=self.mesh,
                           in_specs=P(ax, None), out_specs=P(ax, None))
        self._evict_exchange_programs()
        prog = self._cache[ck] = jax.jit(fn)
        return prog

    def exchange(self, x: jax.Array,
                 pairs: tuple[tuple[int, int], ...]) -> jax.Array:
        """Execute a batch of point-to-point transfers as one ppermute."""
        return self._sendrecv_program(tuple(pairs))(x)

    # Batched p2p windows make the pair-set space combinatorial (any
    # matching can occur); cap the exchange-program entries with FIFO
    # eviction so novel concurrency interleavings cannot pin compiled
    # executables without bound (the other program caches have small
    # closed key spaces and stay uncapped).
    _MAX_EXCHANGE_PROGRAMS = 128

    def _evict_exchange_programs(self):
        # list(dict) snapshots atomically under the GIL; iterating the
        # live dict would race concurrent _program inserts from other
        # launcher threads ("dictionary changed size during iteration")
        keys = [k for k in list(self._cache)
                if k and k[0] in ("exchange", "exchange_flat")]
        while len(keys) > self._MAX_EXCHANGE_PROGRAMS:
            self._cache.pop(keys.pop(0), None)

    def _sendrecv_program_flat(self, pairs: tuple[tuple[int, int], ...]):
        ck = ("exchange_flat", pairs)
        cached = self._cache.get(ck)
        if cached is not None:
            return cached
        ax = self.axis_name

        def g(x):
            return send_recv(x, list(pairs), ax)

        fn = jax.shard_map(_named(g, "exchange"), mesh=self.mesh,
                           in_specs=P(ax), out_specs=P(ax))
        self._evict_exchange_programs()
        prog = self._cache[ck] = jax.jit(fn)
        return prog

    def exchange_flat(self, x: jax.Array,
                      pairs: tuple[tuple[int, int], ...]) -> jax.Array:
        """Flat-layout exchange: global (W*n,), per-device shards are the
        rank-local payloads (the device-resident send/recv path)."""
        return self._sendrecv_program_flat(tuple(pairs))(x)


def _wire_name(wire_dtype) -> str | None:
    return None if wire_dtype is None else jnp.dtype(wire_dtype).name


def _named(fn: Callable, *parts) -> Callable:
    """Give a program's function the stable name ``accl_<part>_...`` (parts
    that are None left out) before it is jitted: the trace's ``XLA
    Modules`` line then shows ``jit_accl_allreduce_xla``. The algorithm
    part is the one the program runs (:meth:`MeshCollectives._resolved`;
    the block-scaled lane is always a ring)."""
    fn.__name__ = fn.__qualname__ = "_".join(
        ["accl", *(str(p) for p in parts if p is not None)])
    return fn
