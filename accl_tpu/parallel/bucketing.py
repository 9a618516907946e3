"""Bucketed data-parallel gradient all-reduce (DDP-style).

BASELINE config 5: Llama-3-8B bucketed DP gradient all-reduce. The
reference's substrate for this is its segmented ring allreduce
(ccl_offload_control.c:942-1098 — segmentation at ``max_segment_size``
keeps the ring pipelined); the training-framework analog is DDP gradient
bucketing: flatten gradient leaves into ~fixed-byte fused buffers in
reverse-layer order (so the first buckets fill while the tail of the
backward pass is still executing), all-reduce each bucket, scatter back.

Everything here is functional and traceable: build a :class:`BucketPlan`
from the pytree's shapes once (host side), then call
:func:`bucketed_allreduce` inside shard_map/pjit. Wire compression per
bucket (bf16/fp16 on the ICI hop, fp32 accumulation) mirrors the
reference's ETH_COMPRESSED lanes.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp


from ..constants import ReduceFunc
from .collectives import ring_allreduce_shard, axis_reduce


@dataclasses.dataclass(frozen=True)
class _Slot:
    leaf_index: int
    offset: int
    size: int
    shape: tuple
    dtype: object


@dataclasses.dataclass(frozen=True)
class Bucket:
    slots: tuple[_Slot, ...]
    nbytes: int
    dtype: object

    @property
    def numel(self) -> int:
        return sum(s.size for s in self.slots)


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Assignment of pytree leaves to fused all-reduce buckets.

    Leaves are walked in *reverse* flatten order (DDP convention: gradients
    for the last layers are ready first during backward) and packed into
    per-dtype buckets of ~``bucket_bytes``.
    """

    buckets: tuple[Bucket, ...]
    treedef: object
    n_leaves: int

    @property
    def total_bytes(self) -> int:
        return sum(b.nbytes for b in self.buckets)

    def describe(self) -> str:
        lines = [f"BucketPlan: {len(self.buckets)} buckets, "
                 f"{self.total_bytes / 1e6:.1f} MB total"]
        for i, b in enumerate(self.buckets):
            lines.append(f"  [{i}] {len(b.slots)} leaves, "
                         f"{b.nbytes / 1e6:.2f} MB, {np.dtype(b.dtype).name}")
        return "\n".join(lines)


def make_bucket_plan(tree, bucket_bytes: int = 25 << 20) -> BucketPlan:
    """Build a plan from a pytree of arrays or ShapeDtypeStructs."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    by_dtype: dict = {}
    for idx in reversed(range(len(leaves))):
        leaf = leaves[idx]
        dt = np.dtype(leaf.dtype)
        by_dtype.setdefault(dt, []).append(idx)

    buckets: list[Bucket] = []
    for dt, idxs in by_dtype.items():
        cur: list[_Slot] = []
        cur_bytes = 0
        for idx in idxs:
            leaf = leaves[idx]
            size = int(np.prod(leaf.shape)) if leaf.shape else 1
            cur.append(_Slot(idx, cur_bytes // dt.itemsize, size,
                             tuple(leaf.shape), dt))
            cur_bytes += size * dt.itemsize
            if cur_bytes >= bucket_bytes:
                buckets.append(Bucket(tuple(cur), cur_bytes, dt))
                cur, cur_bytes = [], 0
        if cur:
            buckets.append(Bucket(tuple(cur), cur_bytes, dt))
    return BucketPlan(tuple(buckets), treedef, len(leaves))


def _flatten_bucket(bucket: Bucket, leaves) -> jnp.ndarray:
    return jnp.concatenate(
        [leaves[s.leaf_index].reshape(-1) for s in bucket.slots])


def _scatter_bucket(bucket: Bucket, fused: jnp.ndarray, out: list):
    for s in bucket.slots:
        out[s.leaf_index] = jax.lax.dynamic_slice_in_dim(
            fused, s.offset, s.size).reshape(s.shape)


def bucketed_allreduce(grads, axis_name: str,
                       plan: BucketPlan | None = None,
                       bucket_bytes: int = 25 << 20,
                       wire_dtype=None,
                       average: bool = True,
                       algorithm: str = "xla",
                       func: ReduceFunc = ReduceFunc.SUM):
    """All-reduce a gradient pytree across ``axis_name`` in fused buckets.

    Runs inside shard_map/pjit. ``wire_dtype`` compresses each bucket on
    the wire (cast before the collective, accumulate handled by the ring
    path hop-wise; the xla path casts once) — the ETH_COMPRESSED analog.
    ``average`` divides by the axis size (DP gradient averaging).
    """
    if plan is None:
        plan = make_bucket_plan(grads, bucket_bytes)
    leaves = jax.tree_util.tree_leaves(grads)
    if len(leaves) != plan.n_leaves:
        raise ValueError(
            f"plan built for {plan.n_leaves} leaves, got {len(leaves)}")
    out: list = [None] * plan.n_leaves
    W = jax.lax.axis_size(axis_name)
    for bucket in plan.buckets:
        fused = _flatten_bucket(bucket, leaves)
        if algorithm == "ring":
            reduced = ring_allreduce_shard(fused, axis_name, func,
                                           wire_dtype)
        else:
            if wire_dtype is not None and fused.dtype != jnp.dtype(wire_dtype):
                reduced = axis_reduce(fused.astype(wire_dtype), axis_name,
                                      func).astype(fused.dtype)
            else:
                reduced = axis_reduce(fused, axis_name, func)
        if average and func == ReduceFunc.SUM:
            reduced = reduced / W
        _scatter_bucket(bucket, reduced, out)
    return jax.tree_util.tree_unflatten(plan.treedef, out)


def make_ddp_train_step(loss_fn, optimizer, axis_name: str = "dp",
                        plan: BucketPlan | None = None,
                        bucket_bytes: int = 25 << 20,
                        wire_dtype=None, algorithm: str = "xla"):
    """Build a shard_map-ready DDP train step with explicit bucketed
    gradient all-reduce.

    ``loss_fn(params, batch) -> scalar`` computes the *local* loss on this
    rank's batch shard; the returned step all-reduces gradients in buckets
    and applies the optimizer with replicated updates. Use inside
    shard_map over ``axis_name`` (params replicated, batch sharded).
    """

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = bucketed_allreduce(grads, axis_name, plan=plan,
                                   bucket_bytes=bucket_bytes,
                                   wire_dtype=wire_dtype,
                                   algorithm=algorithm)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        loss = axis_reduce(loss, axis_name, ReduceFunc.SUM) / \
            jax.lax.axis_size(axis_name)
        return params, opt_state, loss

    return train_step
