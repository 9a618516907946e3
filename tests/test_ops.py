"""Pallas kernel correctness (interpreter mode on the CPU tier)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from accl_tpu.constants import ReduceFunc
from accl_tpu.ops import (cast_lane, combine, compress_fp8, decompress_fp8,
                          flash_attention, wire_compress, wire_decompress)


@pytest.mark.parametrize("func", list(ReduceFunc))
@pytest.mark.parametrize("n", [1, 7, 128, 1000, 4096])
def test_combine_matches_numpy(func, n):
    rng = np.random.default_rng(n)
    a = rng.standard_normal(n).astype(np.float32)
    b = rng.standard_normal(n).astype(np.float32)
    ref = {ReduceFunc.SUM: np.add, ReduceFunc.MAX: np.maximum,
           ReduceFunc.MIN: np.minimum, ReduceFunc.PROD: np.multiply}[func]
    out = np.asarray(combine(jnp.asarray(a), jnp.asarray(b), func))
    np.testing.assert_allclose(out, ref(a, b), rtol=1e-6)


@pytest.mark.parametrize("dtype", ["int32", "bfloat16", "float16"])
def test_combine_dtypes(dtype):
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-100, 100, 300), jnp.dtype(dtype))
    b = jnp.asarray(rng.integers(-100, 100, 300), jnp.dtype(dtype))
    out = combine(a, b, ReduceFunc.SUM)
    assert out.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(out, np.float64),
                               np.asarray(a, np.float64)
                               + np.asarray(b, np.float64))


def test_combine_2d_shape_preserved():
    a = jnp.ones((13, 5), jnp.float32)
    b = jnp.full((13, 5), 2.0, jnp.float32)
    out = combine(a, b)
    assert out.shape == (13, 5)
    np.testing.assert_allclose(np.asarray(out), 3.0)


@pytest.mark.parametrize("wire", ["float16", "bfloat16"])
def test_cast_lane_roundtrip(wire):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal(513).astype(np.float32))
    down = cast_lane(x, wire)
    assert down.dtype == jnp.dtype(wire)
    up = cast_lane(down, jnp.float32)
    np.testing.assert_allclose(np.asarray(up), np.asarray(x),
                               rtol=1e-2, atol=1e-2)


def test_fp8_codec_roundtrip():
    rng = np.random.default_rng(2)
    x = jnp.asarray((rng.standard_normal(1000) * 10).astype(np.float32))
    q, scale = compress_fp8(x)
    assert q.dtype == jnp.float8_e4m3fn and q.shape == x.shape
    back = decompress_fp8(q, scale)
    # e4m3 has ~2 decimal digits; relative error bounded by the format
    np.testing.assert_allclose(np.asarray(back), np.asarray(x),
                               rtol=0.13,
                               atol=float(np.asarray(scale).ravel()[0]) * 0.6)


def test_fp8_codecs_agree_bitwise():
    """The shard-safe jnp codec (fp8_quantize — what the ring hops and the
    fused xla_compressed_* paths call) and the Pallas lane (compress_fp8)
    implement ONE scale/clamp/rounding policy: identical payload bytes and
    identical scale on the same input."""
    import jax
    from accl_tpu.ops import fp8_dequantize, fp8_quantize
    quant_jit = jax.jit(lambda v: fp8_quantize(v, jnp.float8_e4m3fn))
    rng = np.random.default_rng(3)
    for scale_mag in (1e-6, 1.0, 300.0):
        x = jnp.asarray((rng.standard_normal(777) * scale_mag)
                        .astype(np.float32))
        qp, sp = compress_fp8(x)
        qj, sj = quant_jit(x)
        assert float(sp.ravel()[0]) == float(sj)
        np.testing.assert_array_equal(
            np.asarray(qp).view(np.uint8), np.asarray(qj).view(np.uint8))
        np.testing.assert_array_equal(
            np.asarray(decompress_fp8(qp, sp)),
            np.asarray(fp8_dequantize(qj, sj)))


def test_ring_hop_codec_is_the_shared_codec():
    """An fp8-wire allgather over a 2-device mesh must reproduce
    fp8_dequantize(fp8_quantize(shard)) for every shard — proving the
    in-collective codec is the shared one, not a drifted copy. Tolerance
    is 2 f32 ulps: separately-compiled XLA programs may round the final
    dequant multiply differently; the fp8 payload policy itself is pinned
    bitwise by test_fp8_codecs_agree_bitwise."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from accl_tpu.ops import fp8_dequantize, fp8_quantize
    from accl_tpu.parallel.collectives import MeshCollectives

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    devs = jax.devices()[:2]
    mesh = Mesh(np.array(devs), ("rank",))
    mc = MeshCollectives(mesh, "rank")
    rng = np.random.default_rng(4)
    per_rank = [rng.standard_normal(64).astype(np.float32) for _ in range(2)]
    x = mc.shard(per_rank)
    for alg in ("xla", "ring"):
        out = np.asarray(mc.allgather(x, algorithm=alg,
                                      wire_dtype=jnp.float8_e4m3fn))
        for r in range(2):
            expect = fp8_dequantize(*fp8_quantize(jnp.asarray(per_rank[r]),
                                                  jnp.float8_e4m3fn))
            for dst in range(2):
                if alg == "ring" and dst == r:
                    continue  # ring keeps the local chunk unquantized
                np.testing.assert_allclose(
                    out[dst].reshape(2, -1)[r], np.asarray(expect),
                    rtol=3e-7, atol=0,
                    err_msg=f"alg={alg} dst={dst} src={r}")


def test_wire_codec_dispatch():
    x = jnp.linspace(-3, 3, 640, dtype=jnp.float32)
    p, aux = wire_compress(x, jnp.float8_e4m3fn)
    assert aux is not None
    np.testing.assert_allclose(np.asarray(wire_decompress(p, aux, x.dtype)),
                               np.asarray(x), rtol=0.13, atol=0.05)
    p2, aux2 = wire_compress(x, jnp.bfloat16)
    assert aux2 is None and p2.dtype == jnp.bfloat16


from conftest import dense_attention as _dense_attention


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(1, 2, 64, 16), (2, 1, 130, 32)])
def test_flash_attention_matches_dense(causal, shape):
    B, H, S, D = shape
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], shape, jnp.float32)
    k = jax.random.normal(ks[1], shape, jnp.float32)
    v = jax.random.normal(ks[2], shape, jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    ref = _dense_attention(q, k, v, causal)
    # tolerance admits the MXU's bf16 multiply precision on real TPU
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=8e-3, atol=8e-3)


def test_auto_block_invariants():
    """The adaptive block chooser must (a) never pad more than 25% of the
    length beyond what the 128-block floor already pads, and (b) prefer
    exact divisors when the length is short enough for padding to matter
    (past 4x a candidate the marginal pad is accepted for MXU width)."""
    from accl_tpu.ops.attention import _auto_block
    for s in range(1, 4097):
        b = _auto_block(s)
        assert b in (128, 256, 512)
        padded = -(-s // b) * b
        baseline = -(-s // 128) * 128  # the old fixed-block padding
        assert padded - baseline <= s * 0.25, (s, b)
        if s % 512 == 0:
            assert b == 512, (s, b)
        elif s % 256 == 0 and s < 2048:
            assert b == 256, (s, b)


def test_flash_attention_misaligned_blocks():
    """Causal coverage when block_q straddles block_k boundaries: the
    kv-block count must come from the q block's END (block_q=24,
    block_k=32, qi=2 covers queries 48..71 and needs ceil(72/32)=3 kv
    blocks — an aligned-only formula silently drops keys 64..71)."""
    shape = (1, 2, 96, 16)
    ks = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(ks[0], shape, jnp.float32)
    k = jax.random.normal(ks[1], shape, jnp.float32)
    v = jax.random.normal(ks[2], shape, jnp.float32)
    out = flash_attention(q, k, v, causal=True, block_q=24, block_k=32)
    ref = _dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=8e-3, atol=8e-3)
    # auto-selected blocks on a ragged length take the non-padding path
    out2 = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=8e-3, atol=8e-3)


def test_flash_attention_bf16():
    shape = (1, 2, 96, 16)
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in ks)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    ref = _dense_attention(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_gqa_matches_dense(causal):
    """GQA routed in the kernel index maps: K/V carry fewer heads than Q
    and must NEVER be repeat-copied — the result still matches dense
    attention over explicitly repeated heads."""
    B, H, Hkv, S, D = 2, 8, 2, 96, 32
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    rep = H // Hkv
    ref = _dense_attention(q, jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1),
                           causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=8e-3, atol=8e-3)


@pytest.mark.parametrize("hkv,causal", [(8, True), (2, True), (1, False)])
def test_flash_attention_grad_matches_dense(hkv, causal):
    """The custom VJP (FlashAttention-2 recomputation kernels) must
    reproduce dense-attention gradients for dense, GQA, and MQA head
    layouts — this is what lets models train through the fused kernel."""
    B, H, S, D = 1, 8, 80, 16
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, hkv, S, D), jnp.float32)
    rep = H // hkv

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=causal, block_q=16, block_k=32)
        return jnp.sum(jnp.square(o))

    def loss_dense(q, k, v):
        o = _dense_attention(q, jnp.repeat(k, rep, 1),
                             jnp.repeat(v, rep, 1), causal)
        return jnp.sum(jnp.square(o))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    # the f32 reference at f32 precision on every backend (a TPU's
    # default matmul is one bf16 pass)
    with jax.default_matmul_precision("highest"):
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for got, want, name in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3, err_msg=f"d{name}")


def _decode_reference(q, kc, vc, kvlen):
    B, H, S_new, D = q.shape
    Hkv = kc.shape[1]
    kk = jnp.repeat(kc[:, :, :kvlen], H // Hkv, 1)
    vv = jnp.repeat(vc[:, :, :kvlen], H // Hkv, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kk.astype(jnp.float32)) * (D ** -0.5)
    qpos = kvlen - S_new + jnp.arange(S_new)
    mask = jnp.arange(kvlen)[None, :] <= qpos[:, None]
    s = jnp.where(mask[None, None], s, jnp.finfo(jnp.float32).min)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                      vv.astype(jnp.float32))


@pytest.mark.parametrize("s_new,kvlen", [(1, 37), (3, 64), (5, 100), (1, 1)])
def test_flash_decode_matches_dense(s_new, kvlen):
    """Decode kernel over a part-full cache in its native (B, Hkv, T, D)
    layout: dynamic fill length (traced scalar), GQA routing, causal
    offset for chunked prefill, and a cache length that does NOT divide
    the block size (tail blocks are out-of-bounds-masked)."""
    from accl_tpu.ops.attention import flash_decode
    B, H, Hkv, D, T = 2, 8, 2, 32, 100
    ks = jax.random.split(jax.random.key(5), 3)
    kc = jax.random.normal(ks[0], (B, Hkv, T, D), jnp.float32)
    vc = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, s_new, D), jnp.float32)
    out = flash_decode(q, kc, vc, jnp.int32(kvlen), block_k=32)
    ref = _decode_reference(q, kc, vc, kvlen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=8e-3, atol=8e-3)


def test_flash_decode_tiles_long_prefill_rows():
    """A chunked prefill whose q-group rows (group * S_new) exceed one
    512-row block runs as several row blocks: each keeps its own causal
    offset into the new tokens."""
    from accl_tpu.ops.attention import flash_decode
    B, H, Hkv, D, T, s_new, kvlen = 1, 8, 1, 32, 256, 100, 200
    ks = jax.random.split(jax.random.key(7), 3)
    kc = jax.random.normal(ks[0], (B, Hkv, T, D), jnp.float32)
    vc = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, s_new, D), jnp.float32)
    out = flash_decode(q, kc, vc, jnp.int32(kvlen), block_k=64)
    ref = _decode_reference(q, kc, vc, kvlen)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=8e-3, atol=8e-3)


def test_flash_decode_one_program_many_lengths():
    """The fill length is a runtime scalar: ONE compiled program serves
    every decode step (no per-step recompile as the cache fills)."""
    from accl_tpu.ops.attention import flash_decode
    B, H, Hkv, D, T = 1, 4, 2, 16, 64
    ks = jax.random.split(jax.random.key(6), 3)
    kc = jax.random.normal(ks[0], (B, Hkv, T, D), jnp.float32)
    vc = jax.random.normal(ks[1], (B, Hkv, T, D), jnp.float32)
    q = jax.random.normal(ks[2], (B, H, 1, D), jnp.float32)
    fn = jax.jit(lambda q, kc, vc, n: flash_decode(q, kc, vc, n, block_k=16))
    for kvlen in (1, 17, 40, 64):
        out = fn(q, kc, vc, jnp.int32(kvlen))
        ref = _decode_reference(q, kc, vc, kvlen)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=8e-3, atol=8e-3)
    assert fn._cache_size() == 1
