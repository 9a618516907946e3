"""Pipeline parallelism: GPipe-style microbatch schedule over a mesh axis.

Each rank along the ``pp`` axis owns one stage's parameters; activations
flow stage-to-stage with one neighbor ``ppermute`` per step (pure ICI
traffic, the same neighbor-relay substrate as the reference's ring
collectives — fused recv-compute-send, ccl_offload_control.c:473-500 —
with a model stage as the fused compute). The fill/drain schedule runs
``n_micro + W - 1`` steps; every step each rank applies its stage to the
activation it holds, so the steady state keeps all stages busy.

All control flow is static under jit (lax.fori_loop + masked selects): no
data-dependent branching, one compiled program for any depth.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, microbatches: jnp.ndarray,
                   axis_name: str, replicate_out: bool = True) -> jnp.ndarray:
    """Run ``stage_fn(stage_params, x)`` as a W-stage pipeline (shard_map).

    Args:
        stage_fn: pure per-stage function ``(params, x) -> y`` with
            x.shape == y.shape (homogeneous-stage pipelines; wrap ragged
            stages in projections).
        stage_params: this rank's stage parameters (leading stage axis
            already stripped by shard_map).
        microbatches: (n_micro, mb, ...) — the full input, identical or
            sharded; only stage 0 reads it.
        axis_name: the pp mesh axis.
        replicate_out: if True, the (n_micro, mb, ...) outputs (produced on
            the last stage) are replicated to all ranks via a masked psum;
            otherwise non-final ranks return zeros.

    Returns (n_micro, mb, ...) outputs.
    """
    W = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    n_micro = microbatches.shape[0]
    T = n_micro + W - 1
    # activations flow to the next stage
    perm = [(i, (i + 1) % W) for i in range(W)]

    state0 = jnp.zeros_like(microbatches[0])
    out0 = jnp.zeros_like(microbatches)
    # fresh constants are unvarying over the mesh axis; the loop outputs
    # vary — align the carry types up front (same as ring_attention)
    from .collectives import mark_varying
    state0, out0 = (mark_varying(x, axis_name) for x in (state0, out0))

    def step(t, carry):
        state, outputs = carry
        # stage 0 injects microbatch t (clamped index; masked anyway)
        inject = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(t, 0, n_micro - 1), keepdims=False)
        x_in = jnp.where(jnp.logical_and(me == 0, t < n_micro)[..., None],
                         inject.reshape(-1), state.reshape(-1)
                         ).reshape(state.shape)
        # ranks past the fill front / drain tail compute garbage that the
        # masks below discard — the schedule stays static under jit
        y = stage_fn(stage_params, x_in)
        out_idx = t - (W - 1)
        updated = lax.dynamic_update_index_in_dim(
            outputs, y, jnp.clip(out_idx, 0, n_micro - 1), 0)
        keep = jnp.logical_and(me == W - 1, out_idx >= 0)
        outputs = jnp.where(keep.reshape((1,) * outputs.ndim), updated,
                            outputs)
        state = lax.ppermute(y, axis_name, perm)
        return state, outputs

    _, outputs = lax.fori_loop(0, T, step, (state0, out0))
    if replicate_out:
        contrib = jnp.where((me == W - 1).reshape((1,) * outputs.ndim),
                            outputs, jnp.zeros_like(outputs))
        outputs = lax.psum(contrib, axis_name)
    return outputs


@functools.lru_cache(maxsize=None)
def _pipeline_program(stage_fn, mesh: Mesh, axis_name: str,
                      param_keys_ndims: tuple[tuple[str, int], ...]):
    """Jitted shard_map program; stage params carry a leading (W,) stage
    axis sharded over ``axis_name`` (stripped per-shard).

    The cache is keyed on ``stage_fn`` identity: pass a stable module-level
    function (not a per-call lambda/partial), or every call re-traces and
    the cache retains each closure."""
    pspecs = {k: P(axis_name, *([None] * nd)) for k, nd in param_keys_ndims}

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(pspecs, P()),
                       out_specs=P())
    def f(params, mb):
        local = jax.tree.map(lambda x: x[0], params)
        return pipeline_apply(stage_fn, local, mb, axis_name,
                              replicate_out=True)

    return jax.jit(f)


def pipeline_sharded(stage_fn, stacked_params: dict, microbatches,
                     mesh: Mesh, axis_name: str = "pp") -> jax.Array:
    """Global-array entry: ``stacked_params`` is a flat dict whose leaves
    have a leading (W,) stage axis; ``microbatches`` is (n_micro, mb, ...)
    replicated. Returns replicated (n_micro, mb, ...) outputs."""
    keys_ndims = tuple(sorted(
        (k, v.ndim - 1) for k, v in stacked_params.items()))
    placed = {
        k: jax.device_put(v, NamedSharding(
            mesh, P(axis_name, *([None] * (v.ndim - 1)))))
        for k, v in stacked_params.items()}
    mb = jax.device_put(microbatches, NamedSharding(mesh, P()))
    prog = _pipeline_program(stage_fn, mesh, axis_name, keys_ndims)
    return prog(placed, mb)
