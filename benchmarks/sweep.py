"""Per-collective message-size sweeps over a jax mesh.

Each measurement jits ONE shard_map program with K chained, loop-carried
iterations of the collective (so XLA cannot hoist it) and derives
seconds/op from the K slope (timing.slope_time). Results are CSV rows
compatible with benchmarks.elaborate.

Bus-bandwidth accounting follows the standard ring-collective formulas
(the same the reference's throughput columns express per-CCLO,
test/host/test.py:949-950): for total payload S over W ranks,
all-reduce moves 2(W-1)/W * S per chip, all-gather/reduce-scatter and
all-to-all (W-1)/W * S, broadcast/sendrecv S.
"""

from __future__ import annotations

import csv
import dataclasses
import os
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from accl_tpu.constants import ReduceFunc
from accl_tpu.parallel.collectives import (axis_reduce, masked_bcast,
                                           ring_allgather_shard,
                                           ring_allreduce_shard,
                                           ring_reduce_scatter_shard)
from accl_tpu.parallel.tree import (tree_bcast_shard, tree_gather_shard,
                                    tree_scatter_shard)

from .timing import slope_time

CSV_FIELDS = ["collective", "algorithm", "world", "dtype", "wire_dtype",
              "nbytes", "seconds_per_op", "bus_gbps", "units", "tier",
              "tflops", "mfu", "algorithm_source"]
# tflops/mfu are filled by the compute-bound sweeps (attention): achieved
# TFLOP/s and its fraction of the chip's bf16 peak; blank elsewhere
# "units" qualifies the bus_gbps column: "GB/s" (the default) for
# bandwidth rows, "tokens/s" for model-throughput rows (llama sweeps) —
# aggregators must not average across different units
# "algorithm_source" records HOW the algorithm column was decided:
# "forced" (caller pinned it — the default for every explicit sweep) vs
# "chosen" (a tuner resolved AUTO) — so tuned-vs-default comparisons
# stay reproducible from the results file alone


def bus_factor(op: str, W: int) -> float:
    if op == "allreduce":
        return 2 * (W - 1) / W
    if op in ("allgather", "reduce_scatter", "alltoall"):
        return (W - 1) / W
    return 1.0  # bcast, scatter, gather, sendrecv


@dataclasses.dataclass
class SweepResult:
    rows: list[dict]

    def to_csv(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=CSV_FIELDS)
            w.writeheader()
            w.writerows([{"units": "GB/s", "algorithm_source": "forced",
                          **r} for r in self.rows])

    def to_json(self, path: str):
        """Same rows as machine-readable JSON (tuned-vs-default
        comparison records: every row carries algorithm +
        algorithm_source)."""
        import json
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump({"rows": [{"units": "GB/s",
                                 "algorithm_source": "forced", **r}
                                for r in self.rows]}, f, indent=1)
            f.write("\n")

    def table(self) -> str:
        lines = ["{:<16} {:>6} {:>12} {:>14} {:>12} {:>9}".format(
            "collective", "algo", "nbytes", "us/op", "throughput",
            "units")]
        for r in self.rows:
            lines.append(
                "{:<16} {:>6} {:>12} {:>14.1f} {:>12.3f} {:>9}".format(
                    r["collective"], r["algorithm"], r["nbytes"],
                    r["seconds_per_op"] * 1e6, r["bus_gbps"],
                    r.get("units", "GB/s")))
        return "\n".join(lines)


_ALLOWED_ALGOS = {
    "allreduce": {"xla", "ring"}, "allgather": {"xla", "ring"},
    "reduce_scatter": {"xla", "ring"}, "bcast": {"xla", "tree"},
    "scatter": {"tree"}, "gather": {"tree"}, "alltoall": {"xla"},
    "sendrecv": {"xla"},
}


def _iteration(op: str, algorithm: str, ax: str, W: int, me,
               func: ReduceFunc, wire_dtype, root: int = 0,
               axes2d: tuple[str, str] | None = None):
    """Build the shape-preserving per-iteration body x -> x."""
    if algorithm not in _ALLOWED_ALGOS.get(op, set()):
        raise NotImplementedError(
            f"{op} has no '{algorithm}' algorithm "
            f"(supported: {sorted(_ALLOWED_ALGOS.get(op, set()))})")
    scale = 1.0 / W

    if op == "allreduce":
        if algorithm == "ring":
            return lambda x: ring_allreduce_shard(x, ax, func,
                                                  wire_dtype) * scale
        return lambda x: axis_reduce(x, ax, func) * scale
    if op == "allgather":
        # x: own chunk (c,) -> gather (W, c) -> take own chunk back
        if algorithm == "ring":
            def body(x):
                g = ring_allgather_shard(x, ax, wire_dtype)
                return lax.dynamic_index_in_dim(g, me, keepdims=False)
        else:
            def body(x):
                g = lax.all_gather(x, ax)
                return lax.dynamic_index_in_dim(g, me, keepdims=False)
        return body
    if op == "reduce_scatter":
        # x: (W, c) chunks -> own reduced chunk (c,) -> tile back
        if algorithm == "ring":
            def body(x):
                r = ring_reduce_scatter_shard(x, ax, func, wire_dtype)
                return jnp.broadcast_to(r * scale, x.shape)
        else:
            def body(x):
                r = lax.psum_scatter(x.reshape(x.shape[0], -1), ax,
                                     scatter_dimension=0, tiled=False)
                return jnp.broadcast_to(
                    (r * scale).reshape(x.shape[1:]), x.shape)
        return body
    if op == "bcast":
        if algorithm == "tree":
            o, i = axes2d
            return lambda x: tree_bcast_shard(x, root, o, i)
        return lambda x: masked_bcast(x, root, ax)
    if op == "scatter":
        if axes2d is None:
            raise NotImplementedError(
                "scatter sweeps require algorithm='tree' on a 2D mesh")
        o, i = axes2d
        def body(x):  # x: (W, c) at root -> own chunk -> tile back
            mine = tree_scatter_shard(x, root, o, i)
            return jnp.broadcast_to(mine, x.shape)
        return body
    if op == "gather":
        if axes2d is None:
            raise NotImplementedError(
                "gather sweeps require algorithm='tree' on a 2D mesh")
        o, i = axes2d
        def body(x):  # x: own chunk -> (W, c) at root -> own chunk back
            g = tree_gather_shard(x, root, o, i)
            return lax.dynamic_index_in_dim(g, me, keepdims=False) + x * 0
        return body
    if op == "alltoall":
        return lambda x: lax.all_to_all(x, ax, split_axis=0, concat_axis=0,
                                        tiled=False)
    if op == "sendrecv":
        # 2-rank ping-pong: 0 -> 1 then 1 -> 0 (2 hops per iteration)
        def body(x):
            x = lax.ppermute(x, ax, [(0, 1)])
            return lax.ppermute(x, ax, [(1, 0)])
        return body
    raise NotImplementedError(op)


def _shard_shape(op: str, W: int, count: int) -> tuple:
    """Per-rank operand shape for total element count ``count``."""
    if op in ("allgather", "gather"):
        return (max(count // W, 1),)
    if op in ("reduce_scatter", "alltoall", "scatter"):
        c = max(count // W, 1)
        return (W, c)
    return (count,)  # allreduce, bcast, sendrecv


def sweep_collective(mesh: Mesh, op: str, sizes: Sequence[int],
                     algorithm: str = "xla",
                     dtype=jnp.float32, wire_dtype=None,
                     axis_name: str | None = None,
                     func: ReduceFunc = ReduceFunc.SUM,
                     root: int = 0, tier: str = "mesh",
                     reps: int = 5,
                     algorithm_source: str = "forced") -> SweepResult:
    """Sweep ``op`` over total payload ``sizes`` (bytes) on ``mesh``.

    For 2D meshes (tree algorithms) the collective runs over both axes;
    ``axis_name`` defaults to the sole axis (1D) or is ignored (tree).
    ``algorithm_source`` labels each result row with how ``algorithm``
    was decided — "forced" (explicit, the default) vs "chosen" (a tuner
    picked it) — so result files stay self-describing for
    tuned-vs-default comparisons.
    """
    axis_names = tuple(mesh.axis_names)
    axes2d = axis_names if len(axis_names) == 2 else None
    ax = axis_name or axis_names[0]
    W = int(np.prod([mesh.shape[a] for a in axis_names]))
    itemsize = jnp.dtype(dtype).itemsize
    spec = P(axis_names if axes2d else ax, None)
    wire = jnp.dtype(wire_dtype) if wire_dtype else None

    rows = []
    for nbytes in sizes:
        count = max(int(nbytes) // itemsize, W)
        shard_shape = _shard_shape(op, W, count)

        def make_chain(K):
            def shard_fn(x):
                me = lax.axis_index(ax) if axes2d is None else (
                    lax.axis_index(axis_names[0]) * mesh.shape[axis_names[1]]
                    + lax.axis_index(axis_names[1]))
                body = _iteration(op, algorithm, ax, W, me, func, wire,
                                  root, axes2d)
                out = lax.fori_loop(0, K, lambda i, a: body(a), x[0])
                return jnp.sum(out.reshape(-1)[:1])[None]

            f = jax.shard_map(shard_fn, mesh=mesh, in_specs=spec,
                              out_specs=P(spec[0]), check_vma=False)
            return jax.jit(lambda v: f(v)[0])

        x = jax.device_put(
            jnp.full((W,) + shard_shape, 1.0 / W, dtype),
            NamedSharding(mesh, P(*spec)))
        t = slope_time(make_chain, (x,), reps=reps)
        if op == "sendrecv":
            t /= 2  # the iteration body is a 2-hop round trip; report one-way
        gbps = bus_factor(op, W) * count * itemsize / t / 1e9
        rows.append({
            "collective": op, "algorithm": algorithm, "world": W,
            "dtype": jnp.dtype(dtype).name,
            "wire_dtype": jnp.dtype(wire).name if wire else "",
            "nbytes": count * itemsize,
            "seconds_per_op": t, "bus_gbps": round(gbps, 4), "tier": tier,
            "algorithm_source": algorithm_source,
        })
    return SweepResult(rows)
