"""Backend rules shared by the kernels and the entry points.

* :func:`pallas_interpret` — the one rule for how Pallas kernels run:
  Mosaic-compiled on a TPU, interpreted on the CPU tier, and an error
  on any other backend (never a silent interpreter on a device).
* :func:`use_compile_cache` — the persistent compile cache the entry
  points (``chip_smoke.py``, ``bench.py``) place before their first
  compile: ``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
  ``<checkout>/.jax_cache`` (a fixed path, so a later call hits it).
"""

from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def pallas_interpret() -> bool:
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run Mosaic-compiled on tpu or interpreted on "
        f"cpu; the {backend!r} backend has neither path")


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at its one directory and
    return it."""
    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    return path
