"""Data-parallel Llama training step with bucketed gradient all-reduce.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
          python examples/02_ddp_training.py
(8 virtual devices; on a TPU slice drop the env vars.)
"""

import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from accl_tpu.models import Llama, LlamaConfig
from accl_tpu.parallel import make_bucket_plan


def main():
    devs = jax.devices()
    W = len(devs)
    mesh = Mesh(np.asarray(devs), ("dp",))
    print(f"mesh: {W}x data parallel on {devs[0].platform}")

    config = LlamaConfig.tiny(dim=128, n_layers=2, n_heads=4, n_kv_heads=2,
                              ffn_dim=256)
    model = Llama(config)
    params = model.init(jax.random.key(0))
    plan = make_bucket_plan(params, bucket_bytes=1 << 20)
    print("gradient bucket plan:\n" + plan.describe())

    optimizer = optax.adamw(3e-4)
    opt_state = optimizer.init(params)
    with jax.set_mesh(mesh):
        step = jax.jit(model.make_train_step(optimizer, dp="dp"))
        rng = np.random.default_rng(0)
        for it in range(5):
            batch = jax.device_put(
                rng.integers(0, config.vocab_size, (W, 32)).astype(np.int32),
                NamedSharding(mesh, P("dp", None)))
            params, opt_state, loss = step(params, opt_state, batch)
            print(f"step {it}: loss = {float(loss):.4f}")


if __name__ == "__main__":
    main()
