"""Long-context attention: the sequence sharded over a ring.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
          python examples/06_long_context.py
(8 virtual devices; on a TPU slice drop the env vars.)

The sequence is split over the ``sp`` mesh axis; K/V blocks travel the
ring one ppermute neighbor hop at a time (pure ICI traffic) while each
rank's resident queries accumulate online-softmax attention — no rank
ever holds more than S/W keys, so context length scales linearly with
the ring size. Ulysses (all-to-all head parallelism) runs alongside as
the other sequence-parallel schedule, and both are checked against the
dense golden.
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from accl_tpu.parallel.ring_attention import ring_attention_sharded
from accl_tpu.parallel.ulysses import ulysses_attention_sharded


def dense_attention(q, k, v):
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    qpos = jnp.arange(q.shape[2])[:, None]
    kpos = jnp.arange(k.shape[2])[None, :]
    s = jnp.where(kpos <= qpos, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))


def main():
    devs = jax.devices()
    W = len(devs)
    mesh = Mesh(np.asarray(devs), ("sp",))
    # ulysses shards heads over the axis, so H must divide by W
    H = 8 if W <= 8 and 8 % W == 0 else W
    B, S, D = 2, 64 * W, 64
    print(f"ring of {W} {devs[0].platform} devices; "
          f"sequence {S} = {S // W} per rank")

    key = jax.random.key(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, H, S, D), jnp.float32)
    k = jax.random.normal(kk, (B, H, S, D), jnp.float32)
    v = jax.random.normal(kv, (B, H, S, D), jnp.float32)

    golden = dense_attention(q, k, v)

    out_ring = ring_attention_sharded(q, k, v, mesh, "sp")
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(golden),
                               atol=2e-5, rtol=2e-5)
    print("ring attention matches the dense golden")

    out_uly = ulysses_attention_sharded(q, k, v, mesh, "sp")
    np.testing.assert_allclose(np.asarray(out_uly), np.asarray(golden),
                               atol=2e-5, rtol=2e-5)
    print("ulysses attention matches the dense golden")


if __name__ == "__main__":
    main()
