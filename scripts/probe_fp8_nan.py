"""Where does a negative fp8 NaN code lose its sign on the chip?

Reads the e4m3fn codes 0xFF (-NaN), 0x7F (+NaN), 0xFE (-448) and 0x80
(-0), and the e5m2 codes 0xFE (-NaN), 0x7E (+NaN), 0xFC (-inf) and
0x80, back as bytes after each step that could touch them: the
host<->device transfer, an XLA bitcast, an XLA copy, the block-scaled
codec's encoder under XLA, a Pallas kernel that stores them into an
fp8 or an int8 output, and the codec's quantize kernel. One JSON line
per step; a step "keeps" the codes when every byte comes back
unchanged. Runs wherever JAX runs (Pallas interpreted on the CPU):
``PYTHONPATH=. python scripts/probe_fp8_nan.py``.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from accl_tpu.ops import compression as comp
from accl_tpu.utils.platform import pallas_interpret

CODES = {"float8_e4m3fn": [0xFF, 0x7F, 0xFE, 0x80],
         "float8_e5m2": [0xFE, 0x7E, 0xFC, 0x80]}


def _bytes(a) -> list[int]:
    return np.asarray(a).view(np.uint8).reshape(-1)[:4].tolist()


def _kernel_store(bits: jax.Array, out_dtype) -> jax.Array:
    def kernel(b_ref, o_ref):
        b = b_ref[...]
        o_ref[...] = (b if out_dtype == jnp.int8
                      else jax.lax.bitcast_convert_type(b, out_dtype))

    return pl.pallas_call(
        kernel, out_shape=jax.ShapeDtypeStruct(bits.shape, out_dtype),
        interpret=pallas_interpret())(bits)


def main() -> None:
    print(f"device: {jax.devices()[0].device_kind}", flush=True)
    for name, codes in CODES.items():
        qd = jnp.dtype(name)
        u8 = np.resize(np.asarray(codes, np.uint8), (32, 128))
        i8, f8 = u8.view(np.int8), u8.view(qd)
        # f32 inputs whose encode gives these codes, NaN signs kept
        f32 = (f8.astype(np.float32).view(np.uint32)
               & 0x7FFFFFFF | (u8.astype(np.uint32) & 0x80) << 24
               ).view(np.float32)
        steps = {
            "transfer f8 to device and back": lambda: jax.device_put(f8),
            "xla bitcast i8->f8": lambda: jax.jit(
                lambda b: jax.lax.bitcast_convert_type(b, qd))(i8),
            "xla bitcast f8->i8 on device": lambda: jax.jit(
                lambda c: jax.lax.bitcast_convert_type(c, jnp.int8))(
                    jax.device_put(f8)),
            "xla copy of f8 (reshape+slice)": lambda: jax.jit(
                lambda c: c.reshape(-1)[:512])(jax.device_put(f8)),
            "xla encoder -> f8": lambda: jax.jit(
                lambda v: comp._bs_fp8_cast(v, name))(f32),
            "pallas store int8 bits": lambda: _kernel_store(
                jnp.asarray(i8), jnp.int8),
            "pallas store f8 (bitcast in kernel)": lambda: _kernel_store(
                jnp.asarray(i8), qd),
            "pallas f8 store, bitcast to i8 in same jit": lambda: jax.jit(
                lambda b: jax.lax.bitcast_convert_type(
                    _kernel_store(b, qd), jnp.int8))(jnp.asarray(i8)),
            "pallas int8 store, xla bitcast to f8 in same jit": lambda: (
                jax.jit(lambda b: jax.lax.bitcast_convert_type(
                    _kernel_store(b, jnp.int8), qd))(jnp.asarray(i8))),
            "bs_quantize codes": lambda: comp.bs_quantize(
                jnp.asarray(f32.reshape(-1)), qd, 128)[0],
            "bs_quantize codes, bitcast to i8 in same jit": lambda: jax.jit(
                lambda v: jax.lax.bitcast_convert_type(
                    comp.bs_quantize(v, qd, 128)[0], jnp.int8))(
                        jnp.asarray(f32.reshape(-1))),
        }
        for step, fn in steps.items():
            try:
                got = _bytes(fn())
                res = {"keeps": got == codes, "got": got}
            except Exception as e:  # noqa: BLE001 — a probe reports
                res = {"error": f"{type(e).__name__}: {e}"[:300]}
            print(json.dumps({"dtype": name, "step": step, "want": codes,
                              **res}), flush=True)


if __name__ == "__main__":
    main()
