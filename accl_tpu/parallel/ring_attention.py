"""Ring attention: exact long-context attention over a sequence-parallel
mesh axis.

The sequence is sharded over the ``sp`` axis; K/V blocks travel the ring
(one ``lax.ppermute`` neighbor hop per step — pure ICI traffic) while every
rank's resident Q block accumulates attention against each visiting block
with the same online-softmax update rule as ops.attention's flash kernel.
After W hops every Q row has seen the full sequence; no rank ever holds
more than S/W keys, so sequence length scales linearly with the ring.

This is exactly the substrate the reference's ring collectives provide —
fused recv-compute-send relay steps with strided addressing
(ccl_offload_control.c:473-500 fused_recv_reduce_send; survey §5
"long-context") — with attention as the fused compute. Causality is
handled by global position masking, so fully-future blocks contribute
nothing (their hop still moves data — the schedule is static under jit).

Use inside shard_map; ``ring_attention_sharded`` wraps a global array.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _block_update(q, k, v, m, l, acc, q_pos, k_pos, sm_scale, causal):
    """One online-softmax accumulation of q against a (k, v) block.

    Grouped GQA layout: q (B, Hkv, G, Sq, D) — G query heads per KV
    head; k/v (B, Hkv, Skv, D); m/l (B, Hkv, G, Sq, 1); acc
    (B, Hkv, G, Sq, D) fp32. Returns updated (m, l, acc).
    """
    s = jnp.einsum("bngqd,bnkd->bngqk", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = k_pos[None, :] <= q_pos[:, None]          # (Sq, Skv)
        s = jnp.where(mask[None, None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    # safe subtrahend: rows with no valid key yet keep m == -inf; exp of
    # (-inf - finite) underflows to 0 instead of producing NaN
    safe = jnp.where(m_new == _NEG_INF, 0.0, m_new)
    p = jnp.exp(s - safe)
    if causal:
        p = jnp.where(mask[None, None, None], p, 0.0)
    alpha = jnp.exp(jnp.where(m == _NEG_INF, _NEG_INF, m - safe))
    l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jnp.einsum("bngqk,bnkd->bngqd", p,
                                   v.astype(jnp.float32),
                                   preferred_element_type=jnp.float32)
    return m_new, l, acc


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str, causal: bool = True,
                   sm_scale: float | None = None) -> jnp.ndarray:
    """Exact attention with K/V ring-rotated over ``axis_name``.

    q: (B, H, S_local, D); k/v: (B, H_kv, S_local, D) with H_kv dividing
    H — GQA KV heads travel the ring UN-REPEATED (H/H_kv times fewer ICI
    bytes on EVERY hop; the update rule groups each KV head's queries).
    Returns (B, H, S_local, D) in q.dtype.
    """
    W = jax.lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    B, H, S, D = q.shape
    Hkv = k.shape[1]
    if H % Hkv:
        raise ValueError(f"q heads {H} not a multiple of kv heads {Hkv}")
    G = H // Hkv
    q = q.reshape(B, Hkv, G, S, D)
    if sm_scale is None:
        sm_scale = float(D) ** -0.5
    # kv travels to the previous rank each hop: at hop i, rank me holds the
    # block that originated at rank (me + i) % W
    perm = [(j, (j - 1) % W) for j in range(W)]
    q_pos = me * S + jnp.arange(S)

    def body(i, carry):
        kv, m, l, acc = carry
        origin = (me + i) % W
        k_blk, v_blk = kv
        m, l, acc = _block_update(q, k_blk, v_blk, m, l, acc,
                                  q_pos, origin * S + jnp.arange(S),
                                  sm_scale, causal)
        # rotate after compute; the last hop's rotate restores the ring but
        # is dead code XLA can elide only if we skip it explicitly
        kv = lax.cond(
            i < W - 1,
            lambda kv: jax.tree.map(
                lambda x: lax.ppermute(x, axis_name, perm), kv),
            lambda kv: kv, kv)
        return kv, m, l, acc

    m0 = jnp.full((B, Hkv, G, S, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hkv, G, S, 1), jnp.float32)
    acc0 = jnp.zeros((B, Hkv, G, S, D), jnp.float32)
    # fresh constants are unvarying over the mesh axis; the loop outputs
    # vary (they depend on axis_index) — align the carry types up front
    from .collectives import mark_varying
    m0, l0, acc0 = (mark_varying(x, axis_name) for x in (m0, l0, acc0))
    _, m, l, acc = lax.fori_loop(0, W, body, ((k, v), m0, l0, acc0),
                                 unroll=True)
    out = acc / jnp.maximum(l, 1e-30)
    return out.reshape(B, H, S, D).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _ring_program(mesh: Mesh, axis_name: str, causal: bool,
                  sm_scale: float | None):
    """Cache the jitted shard_map program per (mesh, axis, flags) so reuse
    hits jax.jit's trace cache instead of rebuilding the closure."""
    spec = P(None, None, axis_name, None)

    @functools.partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
    def f(q, k, v):
        return ring_attention(q, k, v, axis_name, causal, sm_scale)

    return jax.jit(f)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, axis_name: str = "sp",
                           causal: bool = True,
                           sm_scale: float | None = None) -> jax.Array:
    """Global-array wrapper: q/k/v (B, H, S, D) with S sharded over
    ``axis_name``; runs ring_attention under shard_map."""
    spec = P(None, None, axis_name, None)
    args = [jax.device_put(x, NamedSharding(mesh, spec)) for x in (q, k, v)]
    return _ring_program(mesh, axis_name, causal, sm_scale)(*args)
