"""Codec timing of one tree on one chip, for parent/change A/B runs.

Usage: ``python scripts/codec_ab.py <tree> <label>`` imports
``accl_tpu`` from ``<tree>`` (a checkout, e.g. the parent unpacked with
``git archive``) and prints one JSON line: int8 block-128 quantize,
dequantize and fused combine+requant on 25 MiB of f32, best of 10 warm
calls each, in ms on the host clock. Run the trees in one chip call as
parent, change, change, parent, one process each.
"""

from __future__ import annotations

import json
import sys
import time


def main(tree: str, label: str) -> None:
    sys.path.insert(0, tree)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from accl_tpu.constants import ReduceFunc
    from accl_tpu.ops import compression as comp

    n, block, qd = 25 * (1 << 20) // 4, 128, np.dtype(np.int8)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(n, np.float32))
    other = jnp.asarray(rng.standard_normal(n, np.float32))
    one, qmax = comp._bs_scalars(qd.name)
    quantize = jax.jit(lambda v, a, b: comp.bs_quantize(
        v, qd, block, scalars=(a, b)))
    dequantize = jax.jit(lambda q, s: comp.bs_dequantize(q, s, block))
    combine = jax.jit(lambda q, s, v, a, b: comp.bs_combine_requant(
        q, s, v, ReduceFunc.SUM, qd, block, scalars=(a, b)))

    def best_ms(fn, *args):
        jax.block_until_ready(fn(*args))
        times = []
        for _ in range(10):
            t = time.perf_counter()
            jax.block_until_ready(fn(*args))
            times.append(time.perf_counter() - t)
        return min(times) * 1e3

    q, s = quantize(x, one, qmax)
    print(json.dumps({
        "tree": label,
        "quantize_ms": best_ms(quantize, x, one, qmax),
        "dequantize_ms": best_ms(dequantize, q, s),
        "combine_requant_ms": best_ms(combine, q, s, other, one, qmax),
    }), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2])
