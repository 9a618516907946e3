"""Multi-slice / DCN tier tests (accl_tpu/parallel/multislice.py).

The 8-device virtual CPU mesh stands in for 2 slices x 4 chips; on real
multi-slice hardware the same code routes the outer axis over DCN via
mesh_utils.create_hybrid_device_mesh.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp


from accl_tpu.constants import ReduceFunc
from accl_tpu.parallel import (hierarchical_allreduce_sharded, hybrid_mesh,
                               slice_count)


@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    return hybrid_mesh(ici_shape=(4,), n_slices=2)


def _rank_major(mesh, n, seed=0):
    W = mesh.devices.size
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((W, n)).astype(np.float32))


def test_hybrid_mesh_shape(mesh):
    assert mesh.axis_names == ("dcn", "ici")
    assert mesh.devices.shape == (2, 4)
    assert slice_count(jax.devices("cpu")) == 1  # virtual: one real slice


def test_hierarchical_allreduce_matches_flat_sum(mesh):
    x = _rank_major(mesh, 513)  # odd length exercises the pad path
    out = np.asarray(hierarchical_allreduce_sharded(x, mesh))
    golden = np.sum(np.asarray(x), axis=0)
    for r in range(8):
        np.testing.assert_allclose(out[r], golden, rtol=1e-5,
                                   err_msg=f"rank {r}")


@pytest.mark.parametrize("func", [ReduceFunc.MAX, ReduceFunc.MIN,
                                  ReduceFunc.PROD])
def test_hierarchical_allreduce_nonsum(mesh, func):
    x = _rank_major(mesh, 64, seed=3)
    if func == ReduceFunc.PROD:
        x = jnp.abs(x) + 0.5  # keep products well-conditioned
    out = np.asarray(hierarchical_allreduce_sharded(x, mesh, func=func))
    op = {ReduceFunc.MAX: np.max, ReduceFunc.MIN: np.min,
          ReduceFunc.PROD: np.prod}[func]
    golden = op(np.asarray(x), axis=0)
    np.testing.assert_allclose(out[0], golden, rtol=1e-4)


def test_hierarchical_allreduce_dcn_compression(mesh):
    """bf16 on the DCN hop only: result stays close to fp32 (the slice sum
    is exact; only the cross-slice fold is compressed)."""
    x = _rank_major(mesh, 256, seed=7)
    out = np.asarray(hierarchical_allreduce_sharded(
        x, mesh, wire_dtype=jnp.bfloat16))
    golden = np.sum(np.asarray(x), axis=0)
    np.testing.assert_allclose(out[3], golden, rtol=0.02, atol=0.1)


def test_distributed_init_single_process_noop():
    from accl_tpu.parallel import distributed_init

    assert distributed_init() is False  # no coordinator configured -> noop


def test_dp_grad_sync_over_hybrid_mesh(mesh):
    """The intended composition: model axes on ICI, gradient sync
    hierarchical over (ici, dcn) — a DP step whose loss gradient matches
    the single-device gradient."""
    from accl_tpu.parallel.multislice import hierarchical_allreduce
    from jax.sharding import PartitionSpec as P

    W = 8
    n = 128
    w = np.linspace(-1, 1, n).astype(np.float32)
    batches = np.random.default_rng(5).standard_normal((W, n)) \
        .astype(np.float32)

    def per_rank_grad(w_local, batch):
        # d/dw of 0.5*(w.batch)^2 = (w.batch) * batch
        return jnp.dot(w_local, batch) * batch

    def body(wv, bv):
        # wv is replicated (P(None)): full (n,) on every rank
        g = per_rank_grad(wv, bv[0])
        g = hierarchical_allreduce(g, "ici", "dcn") / W
        return g[None]

    f = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(None), P(("dcn", "ici"))),
        out_specs=P(("dcn", "ici"))))
    # replicate w, shard batches rank-major
    gs = np.asarray(f(jnp.asarray(w), jnp.asarray(batches)))
    golden = np.mean([np.dot(w, b) * b for b in batches], axis=0)
    np.testing.assert_allclose(gs[0], golden, rtol=1e-4, atol=1e-5)
