"""Multi-slice / multi-host meshes: the DCN tier of the fabric.

Parity: the reference's distributed backend is two pluggable Ethernet
stacks (VNx UDP / 100G TCP submodules, .gitmodules:18-24) selected at
runtime (accl.py:383-395) with session management in hardware
(tcp_sessionHandler.cpp). The TPU equivalent has two physically distinct
fabrics: ICI inside a slice (fast, torus) and DCN between slices/hosts
(slower, flat). This module makes that hierarchy explicit:

* :func:`hybrid_mesh` — a mesh with a ``dcn`` outer axis (slices/hosts)
  and one or more ``ici`` inner axes, from
  ``mesh_utils.create_hybrid_device_mesh`` when running on real multi-slice
  hardware, or a plain reshape on a single slice / CPU test mesh.
* :func:`hierarchical_allreduce` — the bandwidth-correct composition:
  reduce-scatter inside the slice (ICI), all-reduce of the owned shard
  across slices (DCN carries 1/ici_size of the payload), all-gather inside
  the slice (ICI). This is how the reference's 2-level "tree over rings"
  BASELINE config generalizes to TPU pods.
* :func:`distributed_init` — ``jax.distributed.initialize`` gating for real
  multi-host runs (the mpirun/rank-env analog of the emulator tier).

Everything composes with ``shard_map`` over the same mesh axes the rest of
``parallel/`` uses, so DP/TP/SP schedules can place their axes on ICI and
keep only gradient sync on DCN.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from jax.sharding import Mesh, PartitionSpec as P

from ..constants import ReduceFunc
from .collectives import axis_reduce

__all__ = ["hybrid_mesh", "hierarchical_allreduce",
           "hierarchical_allreduce_sharded", "distributed_init",
           "slice_count"]


def distributed_init(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> bool:
    """Initialize jax.distributed for a true multi-host run.

    Returns True if initialization happened (or already had), False when
    running single-process (the emulator/CI case). Arguments default to
    the standard env vars (JAX_COORDINATOR_ADDRESS etc.), like the
    reference defaults rank/size from the MPI launcher.
    """
    # NOTE: must not touch jax.process_count()/jax.devices() here — reading
    # them initializes the XLA backends, after which initialize() raises.
    if jax.distributed.is_initialized():
        return True
    if coordinator_address is None and num_processes is None:
        import os
        if "JAX_COORDINATOR_ADDRESS" not in os.environ:
            return False
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return True


def slice_count(devices=None) -> int:
    """Number of distinct slices among ``devices`` (1 on single-slice or
    when the platform does not report slice indices)."""
    devices = devices if devices is not None else jax.devices()
    idx = {getattr(d, "slice_index", 0) for d in devices}
    return len(idx)


def hybrid_mesh(ici_shape: tuple[int, ...] | None = None,
                n_slices: int | None = None,
                ici_axes: tuple[str, ...] = ("ici",),
                dcn_axis: str = "dcn",
                devices=None) -> Mesh:
    """Build a (dcn, *ici) mesh.

    On real multi-slice hardware (devices report ``slice_index``) this uses
    ``mesh_utils.create_hybrid_device_mesh`` so the outer axis crosses DCN
    and inner axes stay inside each slice's ICI torus. On a single slice or
    a CPU test mesh it reshapes devices into the same logical hierarchy —
    the collectives compile identically, which is what the CI tier needs.
    """
    devices = list(devices if devices is not None else jax.devices())
    real_slices = slice_count(devices)
    if n_slices is None:
        n_slices = real_slices if real_slices > 1 else 1
    if ici_shape is None:
        per = len(devices) // max(n_slices, 1)
        ici_shape = (per,)
    if len(ici_axes) != len(ici_shape):
        raise ValueError(
            f"ici_axes {ici_axes} must name every ici_shape axis "
            f"{ici_shape} (pass e.g. ici_axes=('x','y') for a 2-D slice)")
    per_slice = int(np.prod(ici_shape))
    if real_slices > 1:
        from jax.experimental import mesh_utils
        # mesh_shape/dcn_mesh_shape are elementwise factors of the SAME
        # logical axes: axis 0 (dcn) gets all slices and no ICI extent,
        # the inner axes get their ICI extent and no DCN extent. The
        # result is (n_slices, *ici_shape) with axis 0 crossing DCN.
        devs = mesh_utils.create_hybrid_device_mesh(
            (1,) + tuple(ici_shape),
            (n_slices,) + (1,) * len(ici_shape),
            devices=devices)
    else:
        need = n_slices * per_slice
        if need > len(devices):
            raise ValueError(f"hybrid mesh {n_slices}x{ici_shape} needs "
                             f"{need} devices, have {len(devices)}")
        devs = np.asarray(devices[:need]).reshape(
            (n_slices,) + tuple(ici_shape))
    return Mesh(devs, (dcn_axis,) + tuple(ici_axes))


def hierarchical_allreduce(x: jnp.ndarray, ici_axis: str = "ici",
                           dcn_axis: str = "dcn",
                           func: ReduceFunc = ReduceFunc.SUM,
                           wire_dtype=None) -> jnp.ndarray:
    """Per-shard body: 2-level allreduce minimizing DCN traffic.

    Phase 1 (ICI): reduce-scatter — each in-slice rank ends up owning a
    1/ici_size shard of the slice-local sum.
    Phase 2 (DCN): all-reduce of the owned shard across slices — the
    cross-slice fabric carries only 1/ici_size of the payload per rank
    (same principle as the reference's segmented ring: never send more
    than your share over the slow hop).
    Phase 3 (ICI): all-gather restores the full vector.

    ``wire_dtype`` compresses the DCN hop only — the slow fabric is where
    wire precision pays (ACCLCompressionFlags analog).
    """
    W = jax.lax.axis_size(ici_axis)
    n = x.shape[0]
    pad = (-n) % W
    if pad:
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
    if func == ReduceFunc.SUM:
        shard = jax.lax.psum_scatter(x, ici_axis, scatter_dimension=0,
                                     tiled=True)
    else:
        # MAX/MIN/PROD have no fused reduce-scatter: reduce in-slice, then
        # keep this rank's shard so the DCN hop still carries 1/W
        full = axis_reduce(x, ici_axis, func)
        me = jax.lax.axis_index(ici_axis)
        shard_len = x.shape[0] // W
        shard = jax.lax.dynamic_slice_in_dim(full, me * shard_len,
                                             shard_len, axis=0)
    if wire_dtype is not None:
        orig = shard.dtype
        shard = axis_reduce(shard.astype(wire_dtype), dcn_axis,
                            func).astype(orig)
    else:
        shard = axis_reduce(shard, dcn_axis, func)
    out = jax.lax.all_gather(shard, ici_axis, axis=0, tiled=True)
    return out[:n] if pad else out


_PROGRAM_CACHE: dict = {}


def hierarchical_allreduce_sharded(x: jax.Array, mesh: Mesh,
                                   ici_axis: str = "ici",
                                   dcn_axis: str = "dcn",
                                   func: ReduceFunc = ReduceFunc.SUM,
                                   wire_dtype=None) -> jax.Array:
    """Driver-level form: ``x`` is (n_ranks, n) rank-major; every rank gets
    the global reduction. The jitted shard_map program is cached per
    (mesh, axes, func, wire dtype) — jit handles shape/dtype keys — so a
    training loop pays one compile, like the sibling MeshCollectives."""
    if x.shape[0] != mesh.devices.size:
        raise ValueError(
            f"x must be rank-major with shape[0] == mesh size "
            f"({mesh.devices.size}), got {x.shape}")
    key = (mesh, ici_axis, dcn_axis, func,
           None if wire_dtype is None else jnp.dtype(wire_dtype).name)
    run = _PROGRAM_CACHE.get(key)
    if run is None:
        spec = P((dcn_axis, ici_axis))

        def body(s):
            return hierarchical_allreduce(
                s[0], ici_axis, dcn_axis, func, wire_dtype)[None]

        run = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=spec,
                                    out_specs=spec))
        _PROGRAM_CACHE[key] = run
    return run(x)
