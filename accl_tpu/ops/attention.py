"""Flash attention — blockwise online-softmax attention as Pallas kernels.

This is the compute half of the long-context story: the same blockwise
update rule (running max / normalizer / accumulator) that
``parallel.ring_attention`` applies across ICI hops, here applied across
KV blocks inside one chip so scores never materialize in HBM.

Three kernels:

* ``flash_attention`` — training/prefill. The KV axis is a grid
  dimension (``arbitrary``), so K/V blocks stream HBM->VMEM double-
  buffered while the MXU works, VMEM holds only one block per operand
  (sequence length is unbounded), and for causal masks the index map
  clamps to the last needed block so skipped blocks are never fetched.
  GQA is native: K/V carry their own (fewer) heads and the index map
  routes each Q head to its KV head — the repeated-KV copy that GQA
  exists to avoid never materializes.
* its backward pass — FlashAttention-2 style recomputation from the
  saved log-sum-exp: one kernel accumulates dK/dV (grid over KV blocks,
  Q innermost), one accumulates dQ (grid over Q blocks, KV innermost).
  Wired via ``jax.custom_vjp`` so models can train through it.
* ``flash_decode`` — KV-cache decode (q_len << kv_len). Operates on the
  cache's native head-major (B, H_kv, T, D) layout, whose (block_k, D)
  tile per kv head meets the TPU's (8, 128) tiling rule for any H_kv,
  with the fill length as a scalar-prefetch operand: blocks past the
  fill are neither fetched (index map clamps -> the pipeline skips the
  repeat DMA) nor computed (``pl.when``), so a step on a part-full
  cache costs what the FILLED prefix costs, not what max_len costs.

The reference has no attention (it is a collectives library); these
kernels exist because the rebuild's flagship models and ring attention
need a TPU-native fused attention. Everything runs in interpreter mode
off-TPU so the CPU test tiers exercise the identical code.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.platform import pallas_interpret as _interpret

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_LANES = 128  # min lane tile; lse/delta ride in lane-broadcast layout


def _compiler_params(ndims: int):
    """Last grid dim is the streamed (revisiting) one; the rest are
    embarrassingly parallel."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (ndims - 1) + ("arbitrary",))


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    pad = (-x.shape[axis]) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _auto_block(s: int) -> int:
    """Largest block whose padding waste is acceptable for length ``s``:
    512-wide matmuls keep the MXU pipeline full (measured on-chip: 4.4x
    faster than 128 blocks at S=2048, 89 vs 20 TFLOP/s), but a ragged
    length pads to the block multiple, so a big block only pays when it
    divides ``s`` or ``s`` is long enough that the pad is marginal."""
    for b in (512, 256):
        if s % b == 0 or s >= 4 * b:
            return b
    return 128


def _bcast_lanes(x: jax.Array, width: int = _LANES) -> jax.Array:
    """(rows, 1) -> (rows, width), every lane carrying the row value."""
    return jnp.broadcast_to(x, (x.shape[0], width))


def _row_vals(ref_slice: jax.Array) -> jax.Array:
    """Recover (rows, 1) row values from a lane-broadcast (rows, LANES)
    array. All lanes are equal, so a lane-reduce is a relayout-free way
    to land the value back in a (rows, 1) register tile."""
    return jnp.max(ref_slice, axis=-1, keepdims=True)


def _tile_lanes(x: jax.Array, width: int) -> jax.Array:
    """(rows, LANES) lane-broadcast -> (rows, width) for width a
    multiple of LANES (the official-kernel tiling trick), else slice."""
    if width % _LANES == 0:
        return jnp.tile(x, (1, width // _LANES))
    return jnp.broadcast_to(_row_vals(x), (x.shape[0], width))


def _precision(dtype):
    """MXU precision of the kernels' f32 dots: f32 inputs get f32
    products (HIGHEST, Mosaic's fp32 contract), narrower inputs keep the
    single bf16-pass default their dtype already asks for."""
    return jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None


def _dot(a, b, dims, prec):
    """f32-accumulated a . b contracting a's dim dims[0] with b's
    dims[1]."""
    return jax.lax.dot_general(a, b, (((dims[0],), (dims[1],)), ((), ())),
                               precision=prec,
                               preferred_element_type=jnp.float32)


def _sds_for(x: jax.Array):
    """ShapeDtypeStruct factory carrying x's varying-mesh-axes set when
    inside shard_map (check_vma requires it explicit on pallas_call
    out_shapes; plain jit has no vma attribute)."""
    try:
        return functools.partial(jax.ShapeDtypeStruct, vma=jax.typeof(x).vma)
    except (AttributeError, TypeError):
        return jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _block_mask(*, causal, q_off, k_off, bq, bk, skv, sq=None):
    """The ONE copy of the block validity mask shared by the streaming
    kernel, the single-block kernel, and the backward pass' probability
    rebuild: key in bounds, (optionally) query in bounds, causal."""
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_off + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < skv
    if sq is not None:
        mask = jnp.logical_and(mask, q_pos < sq)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    return mask


def _masked_scores(q, k, *, sm_scale, causal, q_off, k_off, skv,
                   prec) -> jax.Array:
    """scale * q @ k^T with the shared block mask applied as -inf."""
    bq, bk = q.shape[0], k.shape[0]
    s = _dot(q, k, (1, 1), prec) * sm_scale
    mask = _block_mask(causal=causal, q_off=q_off, k_off=k_off,
                       bq=bq, bk=bk, skv=skv)
    return jnp.where(mask, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc, *,
                sm_scale: float, causal: bool, block_q: int, block_k: int,
                skv: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    prec = _precision(q_ref.dtype)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    if causal:
        # only kv blocks overlapping [0, (qi+1)*block_q) contribute —
        # computed from the q block's END so a block_q that straddles
        # block_k boundaries cannot under-count
        needed = jnp.minimum(pl.cdiv((qi + 1) * block_q, block_k), nk)
    else:
        needed = nk

    @pl.when(kj < needed)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # (block_q, d)
        k = k_ref[0].astype(jnp.float32)          # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        d = q.shape[-1]
        s = _masked_scores(q, k, sm_scale=sm_scale, causal=causal,
                           q_off=qi * block_q, k_off=kj * block_k,
                           skv=skv, prec=prec)

        m_prev = _row_vals(m_sc[...])             # (block_q, 1)
        l_prev = _row_vals(l_sc[...])
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _tile_lanes(_bcast_lanes(m_new), block_k))
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_sc[...] = (acc_sc[...] * _tile_lanes(_bcast_lanes(alpha), d)
                       + _dot(p, v, (1, 0), prec))
        m_sc[...] = _bcast_lanes(m_new)
        l_sc[...] = _bcast_lanes(l_new)

    @pl.when(kj == nk - 1)
    def _finish():
        l = _row_vals(l_sc[...])
        m = _row_vals(m_sc[...])
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_sc[...] / l_safe).astype(o_ref.dtype)
        # log-sum-exp per q row, lane-broadcast (backward residual)
        lse_ref[0] = _bcast_lanes(m + jnp.log(l_safe))


def _fwd_kernel_single(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                       sm_scale: float, causal: bool, block_q: int,
                       block_k: int, skv: int):
    """One-KV-block specialization (Skv_p == block_k): plain softmax
    with no scratch round trips or online-update bookkeeping — the
    short-sequence regime where that machinery is pure overhead."""
    qi = pl.program_id(1)
    prec = _precision(q_ref.dtype)
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = _masked_scores(q, k, sm_scale=sm_scale, causal=causal,
                       q_off=qi * block_q, k_off=0, skv=skv, prec=prec)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - _tile_lanes(_bcast_lanes(m), block_k))
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    o = _dot(p, v, (1, 0), prec)
    o_ref[0] = (o / l).astype(o_ref.dtype)
    lse_ref[0] = _bcast_lanes(m + jnp.log(l))


def _kv_head_row(bh, n_heads: int, n_kv: int):
    """Map a flat (batch*q_head) grid index to the flat (batch*kv_head)
    row of K/V — the GQA head routing, done in the index map so the
    repeated-KV copy never exists."""
    group = n_heads // n_kv
    return (bh // n_heads) * n_kv + (bh % n_heads) // group


def _fwd(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool,
         sm_scale: float, block_q: int, block_k: int):
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]

    qp = _pad_to(q.reshape(B * H, Sq, D), 1, block_q)
    kp = _pad_to(k.reshape(B * Hkv, Skv, D), 1, block_k)
    vp = _pad_to(v.reshape(B * Hkv, Skv, D), 1, block_k)
    Sq_p, Skv_p = qp.shape[1], kp.shape[1]
    nq, nk = Sq_p // block_q, Skv_p // block_k

    if causal:
        # fetch clamp: blocks past the causal frontier revisit the last
        # needed block, and the pipeline skips the repeat DMA
        def kv_index(bh, qi, kj):
            last = jnp.maximum(
                pl.cdiv((qi + 1) * block_q, block_k) - 1, 0)
            return (_kv_head_row(bh, H, Hkv), jnp.minimum(kj, last), 0)
    else:
        def kv_index(bh, qi, kj):
            return (_kv_head_row(bh, H, Hkv), kj, 0)

    sds = _sds_for(qp)
    if nk == 1:
        # whole KV in one block: the scratch/online-update machinery is
        # pure overhead — run the plain-softmax specialization on a
        # 2-D grid (the committed chip curve's weak short-S regime)
        out, lse = pl.pallas_call(
            functools.partial(_fwd_kernel_single, sm_scale=sm_scale,
                              causal=causal, block_q=block_q,
                              block_k=block_k, skv=Skv),
            out_shape=(sds((B * H, Sq_p, D), q.dtype),
                       sds((B * H, Sq_p, _LANES), jnp.float32)),
            grid=(B * H, nq),
            in_specs=[
                pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, D),
                             lambda bh, qi: (_kv_head_row(bh, H, Hkv),
                                             0, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_k, D),
                             lambda bh, qi: (_kv_head_row(bh, H, Hkv),
                                             0, 0),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((1, block_q, D), lambda bh, qi: (bh, qi, 0),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, block_q, _LANES),
                             lambda bh, qi: (bh, qi, 0),
                             memory_space=pltpu.VMEM),
            ),
            # no scratch, no revisiting: both grid dims are
            # embarrassingly parallel (megacore-partitionable)
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel")),
            interpret=_interpret(),
        )(qp, kp, vp)
        return out[:, :Sq].reshape(B, H, Sq, D), lse
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, skv=Skv),
        out_shape=(sds((B * H, Sq_p, D), q.dtype),
                   sds((B * H, Sq_p, _LANES), jnp.float32)),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_index,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_index,
                         memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, kj: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
    )(qp, kp, vp)
    return out[:, :Sq].reshape(B, H, Sq, D), lse


# ---------------------------------------------------------------------------
# backward (FlashAttention-2: recompute p from the saved lse)
# ---------------------------------------------------------------------------

def _recompute_p(q, k, lse_tile, *, sm_scale, causal, block_q, block_k,
                 qi, kj, sq, skv, prec):
    """Shared bwd step: rebuild the (block_q, block_k) probability block
    from saved lse, with padding + causal masking applied."""
    s = _dot(q, k, (1, 1), prec) * sm_scale
    p = jnp.exp(s - _tile_lanes(lse_tile, block_k))
    mask = _block_mask(causal=causal, q_off=qi * block_q,
                       k_off=kj * block_k, bq=block_q, bk=block_k,
                       skv=skv, sq=sq)
    return jnp.where(mask, p, 0.0)


def _bwd_dkv_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_sc, dv_sc, *, sm_scale: float,
                    causal: bool, block_q: int, block_k: int,
                    sq: int, skv: int):
    kj = pl.program_id(1)
    qi = pl.program_id(2)
    prec = _precision(q_ref.dtype)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    if causal:
        # q blocks strictly before the diagonal see nothing of kv block kj
        first = (kj * block_k) // block_q
    else:
        first = 0

    @pl.when(qi >= first)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        p = _recompute_p(q, k, lse_ref[0], sm_scale=sm_scale,
                            causal=causal, block_q=block_q,
                            block_k=block_k, qi=qi, kj=kj, sq=sq, skv=skv,
                            prec=prec)
        # dv += p^T do ; contraction over the q rows
        dv_sc[...] += _dot(p, do, (0, 0), prec)
        dp = _dot(do, v, (1, 1), prec)
        ds = p * (dp - _tile_lanes(delta_ref[0], block_k))
        dk_sc[...] += sm_scale * _dot(ds, q, (0, 0), prec)

    @pl.when(qi == nq - 1)
    def _finish():
        dk_ref[0] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref,
                   dq_ref, dq_sc, *, sm_scale: float, causal: bool,
                   block_q: int, block_k: int, sq: int, skv: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    prec = _precision(q_ref.dtype)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)

    if causal:
        needed = jnp.minimum(pl.cdiv((qi + 1) * block_q, block_k), nk)
    else:
        needed = nk

    @pl.when(kj < needed)
    def _step():
        q = q_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        p = _recompute_p(q, k, lse_ref[0], sm_scale=sm_scale,
                            causal=causal, block_q=block_q,
                            block_k=block_k, qi=qi, kj=kj, sq=sq, skv=skv,
                            prec=prec)
        dp = _dot(do, v, (1, 1), prec)
        ds = p * (dp - _tile_lanes(delta_ref[0], block_k))
        dq_sc[...] += sm_scale * _dot(ds, k, (1, 0), prec)

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0] = dq_sc[...].astype(dq_ref.dtype)


def _bwd(causal, sm_scale, block_q, block_k, res, dout):
    q, k, v, out, lse = res
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = H // Hkv

    qp = _pad_to(q.reshape(B * H, Sq, D), 1, block_q)
    dop = _pad_to(dout.reshape(B * H, Sq, D), 1, block_q)
    kp = _pad_to(k.reshape(B * Hkv, Skv, D), 1, block_k)
    vp = _pad_to(v.reshape(B * Hkv, Skv, D), 1, block_k)
    Sq_p, Skv_p = qp.shape[1], kp.shape[1]
    nq, nk = Sq_p // block_q, Skv_p // block_k
    # lse from fwd is already (B*H, Sq_p, LANES); delta = rowsum(do * o),
    # lane-broadcast to the same layout
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).reshape(B * H, Sq)
    delta = _pad_to(delta, 1, block_q)
    delta = jnp.broadcast_to(delta[..., None], (B * H, Sq_p, _LANES))

    q_spec = pl.BlockSpec((1, block_q, D),
                          lambda bh, kj, qi: (bh, qi, 0),
                          memory_space=pltpu.VMEM)
    lane_spec = pl.BlockSpec((1, block_q, _LANES),
                             lambda bh, kj, qi: (bh, qi, 0),
                             memory_space=pltpu.VMEM)
    if causal:
        # skipped q blocks revisit the first needed one (DMA elided);
        # ONE clamp function serves q/do and lse/delta specs so they can
        # never desynchronize
        def q_index(bh, kj, qi):
            return (bh, jnp.maximum(qi, (kj * block_k) // block_q), 0)
        q_spec = pl.BlockSpec((1, block_q, D), q_index,
                              memory_space=pltpu.VMEM)
        lane_spec = pl.BlockSpec((1, block_q, _LANES), q_index,
                                 memory_space=pltpu.VMEM)

    def kv_index(bh, kj, qi):
        return (_kv_head_row(bh, H, Hkv), kj, 0)

    sds = _sds_for(qp)
    # dK/dV: per Q-head partials (the group sum happens outside — see
    # docstring note on the GQA backward)
    dk_part, dv_part = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          sq=Sq, skv=Skv),
        out_shape=(sds((B * H, Skv_p, D), jnp.float32),
                   sds((B * H, Skv_p, D), jnp.float32)),
        grid=(B * H, nk, nq),
        in_specs=[q_spec, q_spec,
                  pl.BlockSpec((1, block_k, D), kv_index,
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, block_k, D), kv_index,
                               memory_space=pltpu.VMEM),
                  lane_spec, lane_spec],
        out_specs=(
            pl.BlockSpec((1, block_k, D), lambda bh, kj, qi: (bh, kj, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda bh, kj, qi: (bh, kj, 0),
                         memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
    )(qp, dop, kp, vp, lse, delta)

    def kv_index_q(bh, qi, kj):
        if causal:
            last = jnp.maximum(pl.cdiv((qi + 1) * block_q, block_k) - 1, 0)
            return (_kv_head_row(bh, H, Hkv), jnp.minimum(kj, last), 0)
        return (_kv_head_row(bh, H, Hkv), kj, 0)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, sq=Sq, skv=Skv),
        out_shape=sds((B * H, Sq_p, D), q.dtype),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_index_q,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), kv_index_q,
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, kj: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, _LANES),
                         lambda bh, qi, kj: (bh, qi, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, block_q, D),
                               lambda bh, qi, kj: (bh, qi, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_compiler_params(3),
        interpret=_interpret(),
    )(qp, dop, kp, vp, lse, delta)

    dq = dq[:, :Sq].reshape(B, H, Sq, D)
    dk = dk_part[:, :Skv].reshape(B, Hkv, group, Skv, D).sum(2)
    dv = dv_part[:, :Skv].reshape(B, Hkv, group, Skv, D).sum(2)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, block_q, block_k):
    out, _ = _fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return out


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    out, lse = _fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return out, (q, k, v, out, lse)


_flash.defvjp(_flash_fwd, _bwd)


@functools.partial(jax.jit,
                   static_argnames=("causal", "sm_scale", "block_q",
                                    "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, sm_scale: float | None = None,
                    block_q: int | None = None,
                    block_k: int | None = None) -> jax.Array:
    """Fused attention. q: (B, H, Sq, D); k/v: (B, H_kv, Skv, D) with
    H_kv dividing H (GQA routed in the kernel's index maps — pass
    un-repeated KV heads; H_kv == H is the dense case). Returns
    (B, H, Sq, D) in q.dtype. Differentiable (custom VJP with
    FlashAttention-2 recomputation kernels).

    Default blocks adapt to the sequence lengths (see :func:`_auto_block`);
    pass explicit ``block_q``/``block_k`` to pin them."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    if sm_scale is None:
        sm_scale = float(D) ** -0.5
    block_q = min(block_q or _auto_block(Sq), max(Sq, 8))
    block_k = min(block_k or _auto_block(Skv), max(Skv, 8))
    return _flash(q, k, v, causal, sm_scale, block_q, block_k)


# ---------------------------------------------------------------------------
# decode (q_len << kv_len, GQA, dynamic fill length)
# ---------------------------------------------------------------------------

def _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_sc, l_sc,
                   acc_sc, *, sm_scale: float, block_k: int, rows: int,
                   s_new: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    prec = _precision(q_ref.dtype)
    nk = pl.num_programs(3)
    kvlen = kvlen_ref[0]

    @pl.when(kj == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, _NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    needed = pl.cdiv(kvlen, block_k)

    @pl.when(kj < needed)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)       # (rows, d)
        k = k_ref[0, 0].astype(jnp.float32)       # (block_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        d = q.shape[-1]
        # T need not divide block_k: the last block's tail rows are
        # out-of-bounds reads (undefined — NaN in interpret mode) and
        # 0 * NaN would poison the accumulator through the p @ v matmul,
        # so zero them explicitly (K's tail is neutralized by the mask)
        kv_valid = (kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, d), 0)) < kvlen
        v = jnp.where(kv_valid, v, 0.0)
        s = _dot(q, k, (1, 1), prec) * sm_scale
        # row r of q holds (group g, new-token i) with i = r % s_new at
        # absolute position kvlen - s_new + i; padded rows are garbage
        # and sliced off outside
        row = qi * rows + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 0)
        q_pos = kvlen - s_new + row % s_new
        k_pos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (rows, block_k), 1)
        mask = k_pos <= q_pos                    # implies k_pos < kvlen
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = _row_vals(m_sc[...])
        l_prev = _row_vals(l_sc[...])
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _tile_lanes(_bcast_lanes(m_new), block_k))
        p = jnp.where(mask, p, 0.0)
        l_sc[...] = _bcast_lanes(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True))
        acc_sc[...] = (acc_sc[...] * _tile_lanes(_bcast_lanes(alpha), d)
                       + _dot(p, v, (1, 0), prec))
        m_sc[...] = _bcast_lanes(m_new)

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(_row_vals(l_sc[...]), 1e-30)
        o_ref[0, 0] = (acc_sc[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_k"))
def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 kv_len: jax.Array, sm_scale: float | None = None,
                 block_k: int | None = None) -> jax.Array:
    """KV-cache attention for decode/chunked prefill.

    q: (B, H, S_new, D) — the S_new newest tokens' queries, whose
    absolute positions are ``kv_len - S_new .. kv_len - 1``.
    k_cache/v_cache: (B, H_kv, T, D) in the cache's NATIVE layout (no
    transpose copies), filled through ``kv_len`` (a traced int32 scalar —
    the same compiled program serves every step).  Causal within the new
    tokens. Returns (B, H, S_new, D).

    The fill length rides as a scalar-prefetch operand: cache blocks at
    or past it are neither fetched (clamped index map -> repeat-block
    DMA elision) nor computed (``pl.when``), so the cost of a step
    scales with the filled prefix, not with T."""
    B, H, S_new, D = q.shape
    Hkv, T = k_cache.shape[1], k_cache.shape[2]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    group = H // Hkv
    if sm_scale is None:
        sm_scale = float(D) ** -0.5
    block_k = min(block_k or 512, T)
    nk = pl.cdiv(T, block_k)

    # (B, H, S_new, D) -> (B, Hkv, group*S_new, D): rows of one kv head's
    # q group share that head's streamed K/V blocks. A chunked prefill's
    # rows are tiled in block_q row blocks so VMEM holds one (block_q,
    # block_k) score tile whatever S_new is
    rows = group * S_new
    block_q = min(512, max(8, rows + (-rows) % 8))
    qr = _pad_to(q.reshape(B, Hkv, rows, D), 2, block_q)
    rows_p = qr.shape[2]

    kvlen = jnp.asarray(kv_len, jnp.int32).reshape(1)

    def q_index(b, h, qi, kj, kvlen_ref):
        return (b, h, qi, 0)

    def kv_index(b, h, qi, kj, kvlen_ref):
        last = jnp.maximum(pl.cdiv(kvlen_ref[0], block_k) - 1, 0)
        return (b, h, jnp.minimum(kj, last), 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, Hkv, rows_p // block_q, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), q_index),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
            pl.BlockSpec((1, 1, block_k, D), kv_index),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), q_index),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale,
                          block_k=block_k, rows=block_q, s_new=S_new),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, rows_p, D), q.dtype),
        grid_spec=grid_spec,
        compiler_params=_compiler_params(4),
        interpret=_interpret(),
    )(kvlen, qr, k_cache, v_cache)
    return out[:, :, :rows].reshape(B, H, S_new, D)
