"""TPU-dataplane collective tests on the 8-device virtual CPU mesh.

Both algorithm families (fused XLA ops and decomposed ppermute rings with
the firmware chunk schedule) are checked against numpy goldens, including
wire-compressed variants.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


from accl_tpu.constants import ReduceFunc
from accl_tpu.parallel import MeshCollectives, cpu_mesh

W = 8


@pytest.fixture(scope="module")
def coll():
    return MeshCollectives(cpu_mesh(W), "rank")


def _inputs(n, dtype=np.float32, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal(n).astype(dtype) for _ in range(W)]


@pytest.mark.parametrize("algorithm", ["xla", "ring"])
@pytest.mark.parametrize("n", [8, 100, 4096])
def test_allreduce(coll, algorithm, n):
    ins = _inputs(n)
    x = coll.shard(ins)
    out = np.asarray(coll.allreduce(x, algorithm=algorithm))
    golden = sum(ins)
    for r in range(W):
        np.testing.assert_allclose(out[r], golden, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("algorithm", ["xla", "ring"])
@pytest.mark.parametrize("func,npop", [(ReduceFunc.MAX, np.maximum),
                                       (ReduceFunc.MIN, np.minimum),
                                       (ReduceFunc.PROD, np.multiply)])
def test_allreduce_funcs(coll, algorithm, func, npop):
    ins = _inputs(64, seed=1)
    x = coll.shard(ins)
    out = np.asarray(coll.allreduce(x, func=func, algorithm=algorithm))
    golden = ins[0]
    for v in ins[1:]:
        golden = npop(golden, v)
    np.testing.assert_allclose(out[0], golden, rtol=1e-4)


@pytest.mark.parametrize("algorithm", ["xla", "ring"])
def test_reduce_scatter(coll, algorithm):
    chunk = 16
    ins = _inputs(W * chunk, seed=2)
    x = coll.shard(ins)
    out = np.asarray(coll.reduce_scatter(x, algorithm=algorithm))
    total = sum(ins)
    for r in range(W):
        np.testing.assert_allclose(out[r][:chunk],
                                   total[r * chunk:(r + 1) * chunk],
                                   rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("algorithm", ["xla", "ring"])
def test_allgather(coll, algorithm):
    chunk = 12
    ins = _inputs(chunk, seed=3)
    x = coll.shard(ins)
    out = np.asarray(coll.allgather(x, algorithm=algorithm))
    golden = np.concatenate(ins)
    for r in range(W):
        np.testing.assert_allclose(out[r], golden, rtol=1e-6)


@pytest.mark.parametrize("root", [0, 5])
def test_bcast(coll, root):
    ins = _inputs(33, seed=4)
    x = coll.shard(ins)
    out = np.asarray(coll.bcast(x, root=root))
    for r in range(W):
        np.testing.assert_allclose(out[r], ins[root], rtol=1e-6)


@pytest.mark.parametrize("root", [0, 3])
def test_reduce(coll, root):
    ins = _inputs(21, seed=5)
    x = coll.shard(ins)
    out = np.asarray(coll.reduce(x, root=root))
    np.testing.assert_allclose(out[root], sum(ins), rtol=1e-4, atol=1e-5)
    assert np.all(out[(root + 1) % W] == 0)


@pytest.mark.parametrize("root", [0, 7])
def test_scatter(coll, root):
    chunk = 10
    ins = _inputs(W * chunk, seed=6)
    x = coll.shard(ins)
    out = np.asarray(coll.scatter(x, root=root))
    for r in range(W):
        np.testing.assert_allclose(out[r][:chunk],
                                   ins[root][r * chunk:(r + 1) * chunk],
                                   rtol=1e-6)


def test_gather(coll):
    chunk = 6
    ins = _inputs(chunk, seed=7)
    x = coll.shard(ins)
    out = np.asarray(coll.gather(x, root=2))
    np.testing.assert_allclose(out[2], np.concatenate(ins), rtol=1e-6)


def test_alltoall(coll):
    chunk = 4
    ins = _inputs(W * chunk, seed=8)
    x = coll.shard(ins)
    out = np.asarray(coll.alltoall(x))
    for r in range(W):
        for s in range(W):
            np.testing.assert_allclose(
                out[r][s * chunk:(s + 1) * chunk],
                ins[s][r * chunk:(r + 1) * chunk], rtol=1e-6)


def test_exchange_pairs(coll):
    ins = [np.full(4, float(r), np.float32) for r in range(W)]
    x = coll.shard(ins)
    out = np.asarray(coll.exchange(x, ((0, 1), (1, 0), (4, 5))))
    assert out[1][0] == 0.0
    assert out[0][0] == 1.0
    assert out[5][0] == 4.0
    assert np.all(out[2] == 0)  # no sender -> zeros


@pytest.mark.parametrize("algorithm", ["xla", "ring"])
def test_wire_compressed_allreduce(coll, algorithm):
    ins = _inputs(128, seed=9)
    x = coll.shard(ins)
    out = np.asarray(coll.allreduce(x, algorithm=algorithm,
                                    wire_dtype=jnp.bfloat16))
    np.testing.assert_allclose(out[0], sum(ins), rtol=0.1, atol=0.1)


@pytest.mark.parametrize("wire,tol,ring_tol", [
    (jnp.float16, 5e-3, 5e-3),
    (jnp.bfloat16, 4e-2, 4e-2),
    # fp8 ring re-quantizes partial sums with a fresh absmax scale every
    # hop, compounding over W-1 hops; the xla path quantizes inputs once
    ("float8_e4m3fn", 0.2, 0.6),
])
def test_compressed_ring_xla_numerics_agree(coll, wire, tol, ring_tol):
    """The xla and ring algorithms must agree numerically for
    wire_dtype != None: both decompress before accumulating (the
    reference's clane routing, dma_mover.cpp:44-168), so each stays
    within the uncompressed-accumulation tolerance of the fp32 golden —
    a psum in the wire dtype would instead drift by W-1 rounding steps.
    """
    tols = {"ring": ring_tol, "xla": tol}
    ins = _inputs(256, seed=21)
    x = coll.shard(ins)
    golden = sum(ins)
    scale = np.maximum(np.abs(golden), 1.0)
    for alg in ("ring", "xla"):
        out = np.asarray(coll.allreduce(x, algorithm=alg, wire_dtype=wire))
        assert np.max(np.abs(out[0] - golden) / scale) < tols[alg], alg
    # reduce_scatter: same agreement on the fused phase alone
    chunk = 32
    ins_rs = _inputs(W * chunk, seed=22)
    x_rs = coll.shard(ins_rs)
    total = sum(ins_rs)
    scale_rs = np.maximum(np.abs(total), 1.0)
    for alg in ("ring", "xla"):
        out = np.asarray(coll.reduce_scatter(x_rs, algorithm=alg,
                                             wire_dtype=wire))
        for r in range(W):
            err = np.abs(out[r][:chunk] - total[r * chunk:(r + 1) * chunk])
            assert np.max(err / scale_rs[r * chunk:(r + 1) * chunk]) \
                < tols[alg], alg


def test_compressed_allgather_xla_wire(coll):
    """The fused-path allgather rides the wire compressed (round-trip cast
    only — no arithmetic in the wire dtype)."""
    ins = _inputs(16, seed=23)
    x = coll.shard(ins)
    out = np.asarray(coll.allgather(x, algorithm="xla",
                                    wire_dtype=jnp.float16))
    golden = np.concatenate(ins).astype(np.float16).astype(np.float32)
    np.testing.assert_allclose(out[0], golden, rtol=1e-6)


def test_ring_uneven_padding(coll):
    # n not divisible by W exercises the pad path
    ins = _inputs(37, seed=10)
    x = coll.shard(ins)
    out = np.asarray(coll.allreduce(x, algorithm="ring"))
    np.testing.assert_allclose(out[3], sum(ins), rtol=1e-4, atol=1e-5)


def test_ring_allreduce_fp8_wire():
    """fp8 wire compression on ring hops: per-hop absmax scale rides with
    the payload (EQuARX-style quantized collective). Result approximates
    the fp32 sum within fp8 quantization error."""
    import jax
    import jax.numpy as jnp

    from accl_tpu.parallel.collectives import ring_allreduce_shard
    from jax.sharding import Mesh, PartitionSpec as P

    devs = jax.devices()[:4]
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.asarray(devs), ("r",))
    W = 4
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.uniform(-2, 2, (W, 256)).astype(np.float32))

    def body(s):
        return ring_allreduce_shard(
            s[0], "r", wire_dtype=jnp.float8_e4m3fn)[None]

    out = np.asarray(jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P("r", None), out_specs=P("r", None)))(x))
    golden = np.asarray(x).sum(0)
    # fp8 e4m3 has ~2 decimal digits; scale-corrected error stays small
    np.testing.assert_allclose(out[0], golden, rtol=0.1, atol=0.15)
    # sanity: bf16 wire is much tighter
    def body16(s):
        return ring_allreduce_shard(s[0], "r",
                                    wire_dtype=jnp.bfloat16)[None]
    out16 = np.asarray(jax.jit(jax.shard_map(
        body16, mesh=mesh, in_specs=P("r", None),
        out_specs=P("r", None)))(x))
    assert (np.abs(out16[0] - golden).mean()
            <= np.abs(out[0] - golden).mean() + 1e-6)


def test_fused_stream_collective_single_program():
    """The TPU-tier analog of ACCL's streaming operands (OP0/RES on an AXIS
    stream to a user kernel): producer compute, ring allreduce, and
    consumer compute fused into ONE jitted shard_map program — no
    materialized host buffer between stages, one XLA executable."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from accl_tpu.parallel.collectives import ring_allreduce_shard

    devs = jax.devices()[:4]
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.asarray(devs), ("r",))
    W, n = 4, 128
    x = jnp.asarray(np.random.default_rng(3).standard_normal((W, n))
                    .astype(np.float32))

    def fused(s):
        produced = jnp.tanh(s[0]) * 2.0               # producer "kernel"
        summed = ring_allreduce_shard(produced, "r")  # collective
        return jax.nn.relu(summed - 1.0)[None]        # consumer "kernel"

    prog = jax.jit(jax.shard_map(fused, mesh=mesh, in_specs=P("r", None),
                                 out_specs=P("r", None)))
    out = np.asarray(prog(x))
    golden = np.maximum(np.sum(np.tanh(np.asarray(x)) * 2.0, axis=0) - 1.0,
                        0.0)
    np.testing.assert_allclose(out[0], golden, rtol=1e-5, atol=1e-6)
    # one compiled executable containing the whole pipeline: producer op
    # and ring permutes live in the same module
    hlo = prog.lower(x).compile().as_text().lower()
    assert "tanh" in hlo
    assert "collective-permute" in hlo or "collective_permute" in hlo


def test_multi_axis_ring_allreduce_drives_every_axis():
    """The roofline's full-line-rate claim assumes allreduce traffic
    spreads over EVERY torus axis (docs/ROOFLINE.md assumption 2). The
    multi-axis ring schedule demonstrates it in dryrun form: on a
    (2,2,2) mesh, the compiled program's collective-permute pairs cross
    links in all three axis directions (flattened strides 1, 2, 4), and
    the result matches the sum exactly."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from accl_tpu.parallel.collectives import (
        multi_axis_ring_allreduce_shard)

    if len(jax.devices()) < 8:
        import pytest as _pytest
        _pytest.skip("needs 8 virtual devices")
    mesh = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                ("a", "b", "c"))
    n = 8 * 3 * 4

    def f(x):
        return multi_axis_ring_allreduce_shard(x[0], ("a", "b", "c"))[None]

    g = jax.jit(jax.shard_map(f, mesh=mesh,
                              in_specs=P(("a", "b", "c"), None),
                              out_specs=P(("a", "b", "c"), None)))
    rng = np.random.default_rng(0)
    ins = rng.standard_normal((8, n)).astype(np.float32)
    out = np.asarray(g(jnp.asarray(ins)))
    for r in range(8):
        np.testing.assert_allclose(out[r], ins.sum(0), rtol=1e-5)

    hlo = g.lower(jnp.asarray(ins)).compile().as_text()
    strides = set()
    for m in re.finditer(r"source_target_pairs=\{(.*?)\}\}", hlo,
                         re.DOTALL):
        for p in re.finditer(r"\{(\d+),(\d+)\}", m.group(1) + "}"):
            a, b = int(p.group(1)), int(p.group(2))
            strides.add(min(abs(a - b), 8 - abs(a - b)))
    assert {1, 2, 4} <= strides, (
        f"traffic does not cross every torus axis: strides {strides}")
