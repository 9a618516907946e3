"""Which codec row blocks fit a v5e's scoped VMEM?

Compiles the block-scaled codec kernels (quantize, dequantize,
combine+requant) on a 25 MiB f32 payload at block 128 for a described
``v5e:2x2`` (no chip needed; the ``on-chip-measurement`` guide, §2),
with the row block set to 1, 2 and 4 MiB of f32 per grid step, and
prints which compile and the compiler's reason for each refusal.
``ops/compression._bs_block_rows`` is the largest that all compile.
"""

from __future__ import annotations

import importlib
import os

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from accl_tpu.constants import ReduceFunc

MiB = 1 << 20


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    jax.config.update("jax_enable_compilation_cache", False)
    comp = importlib.import_module("accl_tpu.ops.compression")
    comp._interpret = lambda: False
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    dev = SingleDeviceSharding(topo.devices[0])
    n, block = 25 * MiB // 4, 128
    for mib in (1, 2, 4):
        comp._bs_block_rows = lambda b, mib=mib: max(8, mib * MiB // (4 * b))
        for qname in ("float8_e4m3fn", "int8"):
            qd = jnp.dtype(qname)
            x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=dev)
            q = jax.ShapeDtypeStruct((n,), qd, sharding=dev)
            s = jax.ShapeDtypeStruct((n // block,), jnp.float32, sharding=dev)
            sc = jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=dev)
            jax.clear_caches()
            kernels = {
                "quantize": (lambda v, o, m: comp.bs_quantize(
                    v, qd, block, scalars=(o, m)), (x, sc, sc)),
                "dequantize": (lambda c, t: comp.bs_dequantize(
                    c, t, block), (q, s)),
                "combine_requant": (lambda c, t, v, o, m:
                                    comp.bs_combine_requant(
                                        c, t, v, ReduceFunc.SUM, qd, block,
                                        scalars=(o, m)), (q, s, x, sc, sc)),
            }
            for what, (fn, args) in kernels.items():
                try:
                    jax.jit(fn).lower(*args).compile()
                    verdict = "compiles"
                except Exception as e:  # noqa: BLE001 — reported
                    msg = str(e)
                    at = max(msg.find("Scoped"), 0)
                    verdict = "refused: " + msg[at:at + 120]
                print(f"{mib} MiB {qname} {what}: {verdict}", flush=True)


if __name__ == "__main__":
    main()
