"""Bucketed DP gradient all-reduce (BASELINE config 5 substrate)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from jax.sharding import NamedSharding, PartitionSpec as P

from accl_tpu.constants import ReduceFunc
from accl_tpu.parallel import (bucketed_allreduce, cpu_mesh,
                               make_bucket_plan, make_ddp_train_step)


def small_tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w1": rng.standard_normal((32, 16)).astype(np.float32),
        "b1": rng.standard_normal(16).astype(np.float32),
        "w2": rng.standard_normal((16, 8)).astype(np.float32),
        "emb": rng.standard_normal((64, 32)).astype(np.float32),
    }


def test_plan_covers_all_leaves_once():
    tree = small_tree()
    plan = make_bucket_plan(tree, bucket_bytes=1024)
    seen = sorted(s.leaf_index for b in plan.buckets for s in b.slots)
    assert seen == list(range(plan.n_leaves))
    total = sum(int(np.prod(v.shape)) * 4 for v in tree.values())
    assert plan.total_bytes == total
    assert len(plan.buckets) > 1  # 1 KiB buckets split this tree
    assert "buckets" in plan.describe()


def test_plan_reverse_order():
    """First bucket holds the *last* flatten-order leaves (DDP backward
    readiness order)."""
    tree = {"a": np.zeros(4, np.float32), "z": np.zeros(4, np.float32)}
    plan = make_bucket_plan(tree, bucket_bytes=8)
    first = plan.buckets[0].slots[0].leaf_index
    assert first == plan.n_leaves - 1


def test_plan_groups_by_dtype():
    tree = {"a": np.zeros(4, np.float32), "b": np.zeros(4, np.float16),
            "c": np.zeros(4, np.float32)}
    plan = make_bucket_plan(tree, bucket_bytes=1 << 20)
    for b in plan.buckets:
        leaf_dtypes = {b.dtype}
        assert all(s.dtype == b.dtype for s in b.slots), leaf_dtypes


@pytest.mark.parametrize("algorithm", ["xla", "ring"])
@pytest.mark.parametrize("wire", [None, "bfloat16"])
def test_bucketed_allreduce_matches_mean(algorithm, wire):
    mesh = cpu_mesh(8, axis_names=("dp",))
    W = 8
    trees = [small_tree(seed=r) for r in range(W)]
    stacked = jax.tree.map(lambda *xs: np.stack(xs), *trees)
    sharding = NamedSharding(mesh, P("dp"))

    def shard_fn(t):
        local = jax.tree.map(lambda x: x[0], t)
        out = bucketed_allreduce(local, "dp", bucket_bytes=2048,
                                 wire_dtype=wire, algorithm=algorithm)
        return jax.tree.map(lambda x: x[None], out)

    f = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))
    out = f(jax.device_put(stacked, sharding))
    golden = jax.tree.map(lambda *xs: np.mean(np.stack(xs), 0), *trees)
    tol = 2e-2 if wire else 1e-5
    for k in golden:
        got = np.asarray(out[k])
        for r in range(W):
            np.testing.assert_allclose(got[r], golden[k], rtol=tol,
                                       atol=tol)


def test_prebuilt_plan_and_leaf_mismatch():
    tree = small_tree()
    plan = make_bucket_plan(tree)
    with pytest.raises(ValueError):
        bucketed_allreduce({"only": tree["w1"]}, "dp", plan=plan)


def test_ddp_train_step_matches_fullbatch():
    """DDP step over 4 ranks == single-process step on the full batch."""
    import optax

    mesh = cpu_mesh(4, axis_names=("dp",))
    rng = np.random.default_rng(0)
    params = {"w": rng.standard_normal((8, 4)).astype(np.float32),
              "b": np.zeros(4, np.float32)}
    batch = rng.standard_normal((16, 8)).astype(np.float32)

    def loss_fn(p, x):
        y = x @ p["w"] + p["b"]
        return jnp.mean(y ** 2)

    optimizer = optax.sgd(0.1)
    opt_state = optimizer.init(params)

    # golden: full batch, one process
    loss, grads = jax.value_and_grad(loss_fn)(params, batch)
    updates, _ = optimizer.update(grads, opt_state, params)
    golden = jax.tree.map(lambda p, u: p + u, params, updates)

    step = make_ddp_train_step(loss_fn, optimizer, axis_name="dp",
                               bucket_bytes=64)

    def shard_fn(p, s, x):
        new_p, new_s, l = step(jax.tree.map(lambda a: a, p), s, x)
        return new_p, new_s, l[None]

    f = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(), P("dp")),
        out_specs=(P(), P(), P("dp")),
        check_vma=False))
    batch_sharded = jax.device_put(batch, NamedSharding(mesh, P("dp")))
    new_params, _, losses = f(params, opt_state, batch_sharded)
    for k in golden:
        np.testing.assert_allclose(np.asarray(new_params[k]), golden[k],
                                   rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(losses), float(loss), rtol=1e-5)
