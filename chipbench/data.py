"""Seeded payloads: one integer hash, computed alike by numpy and on the chip.

Every buffer a cell drives is a stream of values named by the run's seed and
a few integer tags (input set, rank, buffer slot). The device makes its
buffers with :func:`values` under ``jax.numpy`` in one jitted call; the
reference makes the same values with numpy, bit for bit, from the seed
alone. So the reference takes nothing the program made.

The hash is the murmur3 finalizer over ``index * golden + key`` in uint32.
Its top 23 bits fill a float32 mantissa in [1, 2), shifted to [-1, 1), and
its low 3 bits scale that by 2**-0 .. 2**-7, as gradients spread over
octaves. Every float32 value carries a full mantissa, so a sum rounds as it
would on real gradients. A configuration in another float type gets the
same values rounded to it (round to nearest even, alike in numpy and XLA).
"""

from __future__ import annotations

import hashlib

import ml_dtypes  # noqa: F401 -- registers bfloat16 and float8 with numpy
import numpy as np

# the float types a configuration may state
DTYPES = ("float32", "bfloat16", "float16")


def dtype(name: str) -> np.dtype:
    """The numpy dtype of a configuration's ``dtype``; any other is an
    error, never a silent float32."""
    if name not in DTYPES:
        raise ValueError(f"dtype {name!r} is not one the benchmark makes "
                         f"values for: {DTYPES}")
    return np.dtype(name)


def stream_key(seed: int, set_: int, rank: int, index: int) -> int:
    """The 32-bit key of one value stream: buffer slot ``index`` of input
    set ``set_`` on ``rank``. Seeds of any size are welcome."""
    text = f"{seed},{set_},{rank},{index}".encode()
    return int.from_bytes(hashlib.blake2b(text, digest_size=4).digest(),
                          "little")


def values(xp, key, n: int, dt=np.float32):
    """``n`` values in [-1, 1) of the stream ``key``, as ``dt``.

    ``xp`` is ``numpy`` or ``jax.numpy``; ``key`` a Python int or a uint32
    scalar (traced under jit)."""
    u32 = xp.uint32
    h = xp.arange(n, dtype=u32) * u32(0x9E3779B1) + xp.asarray(key, u32)
    h = h ^ (h >> u32(16))
    h = h * u32(0x85EBCA6B)
    h = h ^ (h >> u32(13))
    h = h * u32(0xC2B2AE35)
    h = h ^ (h >> u32(16))
    mant = (h >> u32(9)) | u32(0x3F800000)
    scale = (u32(127) - (h & u32(7))) << u32(23)
    if xp is np:
        f, s = mant.view(np.float32), scale.view(np.float32)
    else:
        import jax
        f = jax.lax.bitcast_convert_type(mant, xp.float32)
        s = jax.lax.bitcast_convert_type(scale, xp.float32)
    out = (f - xp.float32(1.5)) * xp.float32(2.0) * s
    return out if np.dtype(dt) == np.float32 else out.astype(dt)
