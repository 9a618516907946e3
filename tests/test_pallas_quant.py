"""Pallas fused block-scale codec: bit-identity to the quant.py reference.

The device tier's quantized ring rides three Pallas kernels
(ops/compression): ``bs_quantize``, ``bs_dequantize``, and the fused
``dequant -> f32-accumulate -> requant`` combine. Every claim here is
BIT-identity, not tolerance — the kernels are held to the same numpy
reference (``quant._np_quantize`` / ``_np_dequant``) that pinned the
native SIMD codec, over the same corpus shapes:

  * dense encode parity: every f16-derived f32 value encodes to the
    exact ml_dtypes RNE code for both fp8 wire dtypes (the XLA
    f32->fp8 convert double-rounds through f16 — the kernel carries its
    own integer-RNE encoder);
  * full 256-code decode parity per fp8 dtype;
  * quantize/dequant/combine over the +-0/NaN/inf-seeded scale-mixed
    corpus for every block size in the [32, 4096] envelope;
  * the shard_mapped quantized rings (MeshCollectives) against a
    numpy ring oracle built from the reference primitives;
  * a device-ring differential vs the emu-tier quantized oracle, real
    hardware only (ACCL_TEST_TPU=1 — the CI device backend is flaky,
    so it never gates).

Everything above the last item runs in Pallas interpret mode under
``JAX_PLATFORMS=cpu`` (tier 1).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import ml_dtypes

from accl_tpu import quant
from accl_tpu.constants import ReduceFunc
from accl_tpu.ops import compression as comp

F8 = np.dtype(ml_dtypes.float8_e4m3fn)
F8W = np.dtype(ml_dtypes.float8_e5m2)
QDTYPES = [np.dtype(np.int8), F8, F8W]
BLOCKS = [32, 64, 128, 256, 512, 1024, 2048, 4096]
NP_FUNC = {ReduceFunc.SUM: np.add, ReduceFunc.MAX: np.maximum,
           ReduceFunc.MIN: np.minimum, ReduceFunc.PROD: np.multiply}


def _corpus(seed=3, n=9000):
    """Scale-mixed values spanning denormal-producing to overflow-
    producing block scales, seeded with the special values whose
    handling the reference pins (NaN-propagating scales, +-0, inf)."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal(n).astype(np.float32)
         * np.float32(10.0) ** rng.integers(-24, 24, n).astype(np.float32))
    specials = np.array([np.inf, -np.inf, np.nan, 0.0, -0.0] * 8,
                        np.float32)
    x = np.concatenate([x, specials])
    rng.shuffle(x)
    return x


def _bits(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    return a.view({1: np.uint8, 2: np.uint16, 4: np.uint32}[a.itemsize])


def _assert_bit_identical(got, ref, what: str):
    got = np.asarray(got)
    ref = np.asarray(ref)
    assert got.dtype == ref.dtype and got.shape == ref.shape, what
    gb, rb = _bits(got), _bits(ref)
    if (jax.default_backend() == "tpu"
            and jnp.issubdtype(ref.dtype, jnp.floating)):
        # XLA on a v5e writes every NaN it touches with the sign bit
        # clear: a bitcast or a copy of an fp8 array, and float ops. The
        # kernels' own stores and the host transfer keep it
        # (scripts/probe_fp8_nan.py). Only that sign bit is excused
        sign = rb.dtype.type(1 << (8 * rb.itemsize - 1))
        rb = np.where(np.isnan(ref.astype(np.float32)), rb & ~sign, rb)
    bad = gb != rb
    assert not bad.any(), (
        f"{what}: {int(bad.sum())}/{bad.size} bit mismatches, first at "
        f"{int(np.argmax(bad))}: got {gb[bad][:4]} ref {rb[bad][:4]}")


# -- encode/decode parity ----------------------------------------------------

@pytest.mark.parametrize("qd", [F8, F8W], ids=lambda d: d.name)
def test_fp8_encode_parity_dense_f16(qd):
    """Every f16 bit pattern, widened to f32, encodes to the exact
    ml_dtypes RNE code — including overflow saturation, the max-normal
    tie, denormals, and NaN/inf sign handling."""
    vals = np.arange(1 << 16, dtype=np.uint16).view(np.float16).astype(
        np.float32)
    ref = vals.astype(qd)
    got = np.asarray(jax.jit(
        lambda v: comp._bs_fp8_cast(v, qd.name))(jnp.asarray(vals)))
    _assert_bit_identical(got, ref, f"encode {qd.name}")


@pytest.mark.parametrize("qd", [F8, F8W], ids=lambda d: d.name)
def test_fp8_decode_parity_256_codes(qd):
    """All 256 wire codes dequantize (at scale 1.0) to the exact
    ml_dtypes f32 widening of the code."""
    codes = np.arange(256, dtype=np.uint8).view(qd)
    ref = codes.astype(np.float32)
    ones = np.ones(quant.n_blocks(256, 32), np.float32)
    got = np.asarray(comp.bs_dequantize(jnp.asarray(codes),
                                        jnp.asarray(ones), 32))
    assert np.isnan(ref).sum() == np.isnan(np.asarray(got)).sum()
    m = ~np.isnan(ref)
    _assert_bit_identical(got[m], ref[m], f"decode {qd.name}")


def _normals(rng, n, lo=0x00800000, hi=0x7F000000):
    return rng.integers(lo, hi, n, dtype=np.int64).astype(
        np.uint32).view(np.float32)


@pytest.mark.parametrize("case", ["general", "by_qmax", "reciprocal",
                                  "subnormal_quotient"])
def test_exact_division_matches_ieee(case):
    """The codec's scale division is IEEE round-to-nearest-even (a TPU's
    own f32 divide is not), subnormal and overflowing quotients
    included."""
    rng = np.random.default_rng(7)
    n = 200_000
    a, b = {
        "general": lambda: (_normals(rng, n), _normals(rng, n)),
        "by_qmax": lambda: (_normals(rng, n, 0x00800000, 0x7F7FFFFF),
                            np.full(n, 448.0, np.float32)),
        "reciprocal": lambda: (np.ones(n, np.float32), _normals(rng, n)),
        "subnormal_quotient": lambda: (_normals(rng, n, hi=0x10000000),
                                       _normals(rng, n, 0x30000000)),
    }[case]()
    got = np.asarray(jax.jit(comp._bs_div)(jnp.asarray(a), jnp.asarray(b)))
    with np.errstate(over="ignore", under="ignore"):
        ref = (a / b).astype(np.float32)
    if jax.default_backend() == "tpu":
        # a TPU flushes subnormal f32 values to zero
        tiny = np.abs(ref) < np.finfo(np.float32).tiny
        ref = np.where(tiny & (got == 0), got, ref)
    _assert_bit_identical(got, ref, f"division {case}")


# -- corpus bit-identity vs the numpy reference ------------------------------

@pytest.mark.parametrize("block", BLOCKS)
@pytest.mark.parametrize("qd", QDTYPES, ids=lambda d: d.name)
def test_corpus_quantize_dequant_bit_identical(qd, block):
    x = _corpus()
    ref_s, ref_q = quant._np_quantize(x, qd, block)
    q, s = comp.bs_quantize(jnp.asarray(x), qd, block)
    _assert_bit_identical(s, ref_s, f"scales {qd.name}/{block}")
    _assert_bit_identical(q, ref_q, f"codes {qd.name}/{block}")
    ref_d = quant._np_dequant(ref_s, ref_q, block)
    got_d = comp.bs_dequantize(q, s, block)
    _assert_bit_identical(got_d, ref_d, f"dequant {qd.name}/{block}")


@pytest.mark.parametrize("func", list(NP_FUNC))
@pytest.mark.parametrize("qd", QDTYPES, ids=lambda d: d.name)
def test_corpus_fused_combine_requant_bit_identical(qd, func):
    """The fused hop kernel == reference dequant, then f32 combine, then
    requantize against FRESH scales — run back to back in numpy."""
    block = 128
    x = _corpus(seed=3)
    other = _corpus(seed=7)
    ref_s, ref_q = quant._np_quantize(x, qd, block)
    q, s = comp.bs_quantize(jnp.asarray(x), qd, block)
    acc = NP_FUNC[func](other, quant._np_dequant(ref_s, ref_q, block))
    ref_s2, ref_q2 = quant._np_quantize(acc, qd, block)
    q2, s2 = comp.bs_combine_requant(q, s, jnp.asarray(other), func, qd,
                                     block)
    _assert_bit_identical(s2, ref_s2, f"requant scales {qd.name}/{func}")
    # MIN/MAX over {+0.0, -0.0} may return either zero (IEEE leaves the
    # sign unspecified; np and XLA pick differently) and fp8 codes keep
    # the zero's sign bit — compare those positions sign-insensitively.
    q2 = np.asarray(q2)
    zero = acc == 0.0
    assert (q2[zero].astype(np.float32) == 0.0).all()
    _assert_bit_identical(q2[~zero], ref_q2[~zero],
                          f"requant codes {qd.name}/{func}")
    # round-closing hop: same fused combine, no requantization. MIN/MAX
    # over {+0.0, -0.0} may return either zero (IEEE leaves the sign
    # unspecified and np.minimum / XLA min pick differently); the sign
    # is invisible once requantized, so compare zero-sign-insensitively.
    out = np.asarray(comp.bs_dequant_combine(q, s, jnp.asarray(other),
                                             func, block))
    nan = np.isnan(acc)
    assert (np.isnan(out) == nan).all()
    keep = ~nan & ~((out == 0.0) & (acc == 0.0))
    _assert_bit_identical(out[keep], acc[keep],
                          f"dequant_combine {qd.name}/{func}")


@pytest.mark.parametrize("block", [32, 4096])
def test_corpus_combine_edge_blocks_bit_identical(block):
    x = _corpus(seed=11)
    other = _corpus(seed=13)
    for qd in QDTYPES:
        ref_s, ref_q = quant._np_quantize(x, qd, block)
        q, s = comp.bs_quantize(jnp.asarray(x), qd, block)
        acc = np.add(other, quant._np_dequant(ref_s, ref_q, block))
        ref_s2, ref_q2 = quant._np_quantize(acc, qd, block)
        q2, s2 = comp.bs_combine_requant(q, s, jnp.asarray(other),
                                         ReduceFunc.SUM, qd, block)
        _assert_bit_identical(s2, ref_s2, f"scales {qd.name}/{block}")
        _assert_bit_identical(q2, ref_q2, f"codes {qd.name}/{block}")


# -- quantized rings vs a numpy ring oracle ----------------------------------

@pytest.fixture(scope="module")
def coll4():
    from accl_tpu.parallel import MeshCollectives, make_mesh
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    return MeshCollectives(make_mesh((4,), ("rank",),
                                     devices=jax.devices()[:4]), "rank")


def _finite_inputs(w, n, seed):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal(n).astype(np.float32)
             * np.float32(10.0) ** rng.integers(-3, 4, n).astype(
                 np.float32)) for _ in range(w)]


@pytest.mark.parametrize("qd,func", [(F8, ReduceFunc.SUM),
                                     (np.dtype(np.int8), ReduceFunc.MAX)],
                         ids=["e4m3-sum", "int8-max"])
def test_mesh_ring_allreduce_matches_oracle(coll4, qd, func):
    W, n, block = 4, 513, 64
    ins = _finite_inputs(W, n, 21)
    x = coll4.shard(ins)
    out = np.asarray(coll4.allreduce(x, func=func, algorithm="ring",
                                     wire_dtype=qd, qblock=block))
    ref = quant.ring_allreduce_reference(ins, func, qd, block)
    for r in range(W):
        _assert_bit_identical(out[r], ref[r], f"allreduce rank {r}")


def test_mesh_ring_reduce_scatter_and_allgather_match_oracle(coll4):
    W, n, block, qd = 4, 128, 32, F8
    rows = _finite_inputs(W, W * n, 31)
    x = coll4.shard(rows)
    out = np.asarray(coll4.reduce_scatter(
        x, func=ReduceFunc.SUM, algorithm="ring", wire_dtype=qd,
        qblock=block))
    chunks = [r.reshape(W, n) for r in rows]
    ref = quant.ring_reduce_scatter_reference(chunks, ReduceFunc.SUM, qd,
                                              block)
    for r in range(W):
        _assert_bit_identical(out[r], ref[r], f"reduce_scatter rank {r}")

    mine = [np.asarray(out[r]) for r in range(W)]
    agx = coll4.shard(mine)
    ag = np.asarray(coll4.allgather(agx, algorithm="ring", wire_dtype=qd,
                                    qblock=block))
    agref = quant.ring_allgather_reference(mine, qd, block)
    for r in range(W):
        _assert_bit_identical(ag[r], agref[r], f"allgather rank {r}")


def test_bs_lane_requires_ring_eligibility():
    """qblock=0 or a non-quantizable wire must stay OFF the bs lane."""
    from accl_tpu.parallel.collectives import MeshCollectives
    ok = MeshCollectives._bs_eligible
    assert ok("allreduce", "int8", 64)
    assert ok("reduce_scatter", "float8_e4m3fn", 128)
    assert ok("allgather", "float8_e5m2", 32)
    assert not ok("allreduce", "int8", 0)        # no block -> plain wire
    assert not ok("allreduce", "float16", 64)    # cast lane, not bs
    assert not ok("alltoall", "int8", 64)        # no bs schedule
    assert not ok("bcast", "int8", 64)


# -- device-ring differential (real hardware only, never a CI gate) ----------

@pytest.mark.skipif(not os.environ.get("ACCL_TEST_TPU"),
                    reason="real-chip differential (ACCL_TEST_TPU=1)")
def test_device_ring_vs_emu_quantized_oracle():
    """Driver-level differential on real devices: the device-tier
    quantized ring against the emu-tier quantized executor on identical
    inputs. The tiers use different hop schedules so the comparison is
    the shared per-hop error bound, not bitwise."""
    from accl_tpu.device.tpu import tpu_world
    from accl_tpu.testing import emu_world, run_ranks

    W, count = 4, 513
    if len(jax.devices()) < W:
        pytest.skip(f"needs {W} devices")
    ins = _finite_inputs(W, count, 41)

    def body(a):
        src = a.buffer(data=ins[a.rank].copy())
        dst = a.buffer((count,), np.float32)
        a.allreduce(src, dst, count, compress_dtype=F8, block_scale=64,
                    algorithm="ring")
        return dst.data.copy()

    ew = emu_world(W)
    try:
        emu_out = run_ranks(ew, body)
    finally:
        for a in ew:
            a.deinit()
    tw = tpu_world(W)
    try:
        dev_out = run_ranks(tw, body)
    finally:
        for a in tw:
            a.deinit()
    bound = np.abs(np.stack(ins)).sum(0).max() * 0.07 + 1e-3
    golden = sum(ins)
    for r in range(W):
        assert np.abs(dev_out[r] - golden).max() < bound
        assert np.abs(emu_out[r] - golden).max() < bound
        # both tiers quantized the wire (distinguishable from exact)
        assert np.abs(dev_out[r] - golden).max() > 0
