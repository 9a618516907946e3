#!/usr/bin/env python
"""Lint: every Pallas-kernel module must import (and trace) without a
TPU backend.

Wired into ``make lint``. The device tier's kernels (fused block-scale
codec, combine engine, ring attention) are written to run under
``JAX_PLATFORMS=cpu`` in interpret mode — that is what tier 1 tests and
what the bench microladder gates. A module that drags in a TPU-only
symbol at import time (``pltpu.CompilerParams`` resolved eagerly, a
``jax.devices("tpu")`` probe, a top-level ``pallas_call`` trace against
a TPU mesh) breaks every CPU-only consumer at once and the failure
surfaces far from the edit. This gate pins the contract where it is
cheap: import each module on a CPU-only process, then push one tiny
batch through the fused codec entry points in interpret mode.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MODULES = (
    "accl_tpu.ops.compression",
    "accl_tpu.ops.combine",
    "accl_tpu.ops.attention",
    "accl_tpu.parallel.collectives",
    "accl_tpu.parallel.ulysses",
    "accl_tpu.models.llama",
    "accl_tpu.utils.platform",
)


def main() -> int:
    import importlib

    failed = 0
    for name in MODULES:
        try:
            importlib.import_module(name)
        except Exception as e:  # noqa: BLE001 — lint reports, not raises
            print(f"FAIL: {name} does not import without a TPU backend: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            failed += 1
    if failed:
        return failed

    # the fused codec must also TRACE and run on CPU (interpret mode):
    # an import-clean module whose kernel only compiles on TPU would
    # pass the loop above and still break tier 1
    import numpy as np
    import jax.numpy as jnp
    import ml_dtypes

    from accl_tpu import quant
    from accl_tpu.constants import ReduceFunc
    from accl_tpu.ops import compression as comp

    f8 = np.dtype(ml_dtypes.float8_e4m3fn)
    x = np.linspace(-4.0, 4.0, 256, dtype=np.float32)
    q, s = comp.bs_quantize(jnp.asarray(x), f8, 32)
    ref_s, ref_q = quant._np_quantize(x, f8, 32)
    if (np.asarray(q).tobytes() != ref_q.tobytes()
            or np.asarray(s).tobytes() != ref_s.tobytes()):
        print("FAIL: interpret-mode bs_quantize diverged from the "
              "quant.py reference on the smoke batch", file=sys.stderr)
        return 1
    comp.bs_combine_requant(q, s, jnp.asarray(x), ReduceFunc.SUM, f8, 32)
    comp.bs_dequant_combine(q, s, jnp.asarray(x), ReduceFunc.SUM, 32)
    print(f"pallas import gate: {len(MODULES)} modules clean, fused "
          f"codec traces on CPU")
    return 0


if __name__ == "__main__":
    sys.exit(main())
