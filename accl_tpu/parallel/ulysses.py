"""Ulysses-style sequence parallelism: all-to-all head/sequence re-sharding.

The alternative long-context strategy to the ring: instead of rotating KV
around the mesh, one ``lax.all_to_all`` re-shards activations from
sequence-parallel (every rank: all heads, S/W tokens) to head-parallel
(every rank: H/W heads, all tokens), attention runs fully local per head
group, and a second all-to-all restores sequence sharding. Two all-to-alls
per attention layer vs W ppermute hops for the ring — better for moderate
sequence lengths on all-to-all-rich ICI topologies; the ring wins when
S/W no longer fits or W is large.

The reference's substrate for this is the same 11-op surface (its XRT
enums reserve alltoall; survey §2.9); on TPU it is one fused XLA
collective.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.attention import flash_attention


def seq_to_heads(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """(B, H, S_local, D) seq-sharded -> (B, H/W, S_global, D) head-sharded.
    Requires H % W == 0."""
    W = jax.lax.axis_size(axis_name)
    B, H, S, D = x.shape
    assert H % W == 0, f"heads {H} not divisible by axis size {W}"
    # split heads across ranks, gather sequence: all_to_all moves the head
    # chunks out and concatenates the sequence chunks in
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def heads_to_seq(x: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """Inverse of seq_to_heads: (B, H/W, S_global, D) -> (B, H, S_local, D)."""
    return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                          tiled=True)


def ulysses_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      axis_name: str, causal: bool = True,
                      sm_scale: float | None = None) -> jnp.ndarray:
    """Attention over the full sequence via head-parallel re-sharding.

    q: (B, H, S_local, D); k/v: (B, H_kv, S_local, D) with H_kv dividing
    H — GQA KV heads ride the all-to-all UN-repeated whenever they split
    over the ranks (H/H_kv times fewer wire bytes for K and V; the flash
    kernel routes each Q head to its KV head on the other side). When
    H_kv doesn't divide the axis size, KV repeats minimally (to one head
    per rank if that divides, else to H). Returns (B, H, S_local, D)."""
    W = jax.lax.axis_size(axis_name)
    H, Hkv = q.shape[1], k.shape[1]
    qh = seq_to_heads(q, axis_name)
    if Hkv % W and H != Hkv:
        # KV heads don't split evenly over the ranks: repeat minimally —
        # up to W heads when that divides (one kv head per rank), else
        # all the way to H (the old fully-repeated layout)
        rep = (W // Hkv) if W % Hkv == 0 else (H // Hkv)
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    kh = seq_to_heads(k, axis_name)
    vh = seq_to_heads(v, axis_name)
    out = flash_attention(qh, kh, vh, causal=causal, sm_scale=sm_scale)
    return heads_to_seq(out, axis_name)


@functools.lru_cache(maxsize=None)
def _ulysses_program(mesh: Mesh, axis_name: str, causal: bool,
                     sm_scale: float | None):
    spec = P(None, None, axis_name, None)

    # check_vma=False: the pallas interpreter's internal slices don't carry
    # varying-axis types yet (jax suggests this exact workaround)
    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
    def f(q, k, v):
        return ulysses_attention(q, k, v, axis_name, causal, sm_scale)

    return jax.jit(f)


def ulysses_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                              mesh: Mesh, axis_name: str = "sp",
                              causal: bool = True,
                              sm_scale: float | None = None) -> jax.Array:
    """Global-array wrapper mirroring ring_attention_sharded."""
    spec = P(None, None, axis_name, None)
    args = [jax.device_put(x, NamedSharding(mesh, spec)) for x in (q, k, v)]
    return _ulysses_program(mesh, axis_name, causal, sm_scale)(*args)
