"""Ring attention + Ulysses sequence parallelism vs dense attention."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


from accl_tpu.parallel import (cpu_mesh, ring_attention_sharded,
                               ulysses_attention_sharded, seq_to_heads,
                               heads_to_seq)
from conftest import dense_attention as _dense


def _qkv(shape, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(kk, shape, dtype) for kk in ks)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("W", [4, 8])
def test_ring_attention_matches_dense(causal, W):
    mesh = cpu_mesh(W, axis_names=("sp",))
    B, H, S, D = 2, 4, 16 * W, 16
    q, k, v = _qkv((B, H, S, D))
    out = ring_attention_sharded(q, k, v, mesh, "sp", causal=causal)
    ref = _dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_attention_bf16():
    mesh = cpu_mesh(4, axis_names=("sp",))
    q, k, v = _qkv((1, 2, 64, 32), seed=3, dtype=jnp.bfloat16)
    out = ring_attention_sharded(q, k, v, mesh, "sp", causal=True)
    assert out.dtype == jnp.bfloat16
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_dense(causal):
    W = 4
    mesh = cpu_mesh(W, axis_names=("sp",))
    B, H, S, D = 2, 8, 64, 16  # H divisible by W
    q, k, v = _qkv((B, H, S, D), seed=1)
    out = ulysses_attention_sharded(q, k, v, mesh, "sp", causal=causal)
    ref = _dense(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_seq_head_reshard_roundtrip():
    W = 4
    mesh = cpu_mesh(W, axis_names=("sp",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    x = jax.random.normal(jax.random.key(2), (2, 8, 32, 4))
    spec = P(None, None, "sp", None)
    xs = jax.device_put(x, NamedSharding(mesh, spec))

    def f(x):
        y = seq_to_heads(x, "sp")
        return heads_to_seq(y, "sp")

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=spec,
                                out_specs=spec))(xs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


def test_ring_attention_long_context_scales():
    """S = 8x the per-rank block; exactness is the point of ring attention."""
    mesh = cpu_mesh(8, axis_names=("sp",))
    B, H, S, D = 1, 2, 512, 8
    q, k, v = _qkv((B, H, S, D), seed=4)
    out = ring_attention_sharded(q, k, v, mesh, "sp", causal=True)
    ref = _dense(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("hkv", [8, 4, 2])
def test_ulysses_gqa_unrepeated_kv(hkv):
    """GQA KV heads ride the ulysses all-to-all UN-repeated whenever they
    split over the ranks (H/H_kv fewer wire bytes for K and V): results
    match dense attention over repeated heads for every regime — even
    split (hkv=8), repeat-to-W (hkv=4 on W=8), repeat-to-W (hkv=2)."""
    import jax

    from conftest import dense_attention

    mesh = cpu_mesh(8, axis_names=("sp",))
    H, S, D = 16, 64, 16
    ks = jax.random.split(jax.random.key(11), 3)
    q = jax.random.normal(ks[0], (2, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (2, hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (2, hkv, S, D), jnp.float32)
    out = ulysses_attention_sharded(q, k, v, mesh, "sp", causal=True)
    ref = dense_attention(q, jnp.repeat(k, H // hkv, 1),
                          jnp.repeat(v, H // hkv, 1), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    if hkv == 8:
        # the even-split case must actually move the SMALL kv tensors:
        # the compiled program contains an all-to-all whose operand
        # carries hkv (not H) heads
        from accl_tpu.parallel.ulysses import _ulysses_program
        from jax.sharding import NamedSharding, PartitionSpec as P
        spec = P(None, None, "sp", None)
        args = [jax.device_put(x, NamedSharding(mesh, spec))
                for x in (q, k, v)]
        hlo = _ulysses_program(mesh, "sp", True, None).lower(
            *args).compile().as_text()
        import re
        shapes = {tuple(map(int, m.group(1).split(",")))
                  for m in re.finditer(r"f32\[([\d,]+)\]\S* all-to-all",
                                       hlo)}
        assert any(s[1] == hkv // 8 for s in shapes if len(s) == 4), (
            f"no small-kv all-to-all found: {shapes}")


@pytest.mark.parametrize("hkv", [4, 2, 1])
def test_ring_attention_gqa_unrepeated_kv(hkv):
    """GQA KV heads travel the ring UN-repeated: H/H_kv fewer ICI bytes
    on every hop, results identical to dense attention over repeated
    heads (including the MQA extreme)."""
    import jax

    from conftest import dense_attention

    mesh = cpu_mesh(4, axis_names=("sp",))
    H, S, D = 8, 64, 16
    ks = jax.random.split(jax.random.key(12), 3)
    q = jax.random.normal(ks[0], (2, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (2, hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (2, hkv, S, D), jnp.float32)
    out = ring_attention_sharded(q, k, v, mesh, "sp", causal=True)
    ref = dense_attention(q, jnp.repeat(k, H // hkv, 1),
                          jnp.repeat(v, H // hkv, 1), True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
