"""Compile-only rehearsal of the Pallas kernels for a described TPU v5e.

The TPU compiler is installed on the CPU tier, so each kernel of the
main path compiles here for a ``v5e:2x2`` that is described, not
attached (the ``on-chip-measurement`` guide, §2). That catches what
interpret mode cannot: block shapes that break the (8, 128) tiling
rule, casts Mosaic has no lowering for, kernels over their VMEM budget.
Shapes are the ones users run: a 256 MiB combine, the codec on a 25 MiB
DDP bucket, attention and decode at Llama-3-8B head geometry (32 q / 8
kv heads, d=128), and the fp8 block-scaled ring on four chips.

Nothing here runs a kernel or reports a time. The topology is described
inside a fixture (never at import: one process at a time may load the
TPU library, and every xdist worker imports this file), and each test
steers the kernel modules off interpret mode with ``monkeypatch``.
"""

from __future__ import annotations

import importlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from accl_tpu.constants import ReduceFunc

MiB = 1 << 20
KERNEL_MODULES = ("accl_tpu.ops.combine", "accl_tpu.ops.compression",
                  "accl_tpu.ops.attention")


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def mosaic(monkeypatch):
    """Kernels compile through Mosaic, and the persistent cache is off
    (an entry compiled for a described chip cannot be read back)."""
    from jax.experimental.compilation_cache import compilation_cache
    for name in KERNEL_MODULES:
        monkeypatch.setattr(importlib.import_module(name), "_interpret",
                            lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def test_combine_256mib_f32(one_chip):
    comb = importlib.import_module("accl_tpu.ops.combine")
    x = jax.ShapeDtypeStruct((65536, 1024), jnp.float32, sharding=one_chip)
    _compiled_kernel(lambda a, b: comb.combine_pallas(a, b, ReduceFunc.SUM),
                     x, x)


@pytest.mark.parametrize("qname", ["float8_e4m3fn", "float8_e5m2", "int8"])
def test_block_scaled_codec_25mib(one_chip, qname):
    comp = importlib.import_module("accl_tpu.ops.compression")
    n, block = 25 * MiB // 4, 128
    qd = jnp.dtype(qname)
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((n,), qd, sharding=one_chip)
    s = jax.ShapeDtypeStruct((n // block,), jnp.float32, sharding=one_chip)
    sc = jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=one_chip)
    _compiled_kernel(lambda v, o, m: comp.bs_quantize(
        v, qd, block, scalars=(o, m)), x, sc, sc)
    _compiled_kernel(lambda c, t: comp.bs_dequantize(c, t, block), q, s)
    _compiled_kernel(lambda c, t, v, o, m: comp.bs_combine_requant(
        c, t, v, ReduceFunc.SUM, qd, block, scalars=(o, m)),
        q, s, x, sc, sc)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_flash_attention_fwd_bwd_llama3_8b_heads(one_chip, dtype):
    """bf16 inputs take the MXU's one-pass dots, f32 inputs its f32
    (HIGHEST) contract."""
    att = importlib.import_module("accl_tpu.ops.attention")
    q = jax.ShapeDtypeStruct((1, 32, 2048, 128), dtype, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, 2048, 128), dtype, sharding=one_chip)

    def loss(q, k, v):
        o = att.flash_attention(q, k, v, causal=True)
        return jnp.sum(jnp.square(o.astype(jnp.float32)))

    _compiled_kernel(lambda q, k, v: att.flash_attention(q, k, v), q, kv, kv)
    _compiled_kernel(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)


@pytest.mark.parametrize("s_new", [1, 1024], ids=["decode", "prefill"])
def test_flash_decode_hkv8(one_chip, s_new):
    """Head-major (B, Hkv, T, D) cache at Hkv=8: a decode step and a
    1024-token prefill chunk (whose q rows are tiled into row blocks)."""
    att = importlib.import_module("accl_tpu.ops.attention")
    B, T = 2, 1040
    q = jax.ShapeDtypeStruct((B, 32, s_new, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((B, 8, T, 128), jnp.bfloat16,
                              sharding=one_chip)
    n = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compiled_kernel(att.flash_decode, q, kv, kv, n)


def test_fp8_block_scaled_ring_on_four_chips(topo):
    from accl_tpu.parallel.collectives import MeshCollectives
    mesh = Mesh(np.asarray(topo.devices), ("rank",))
    coll = MeshCollectives(mesh, "rank")
    n = 25 * MiB // 4
    x = jax.ShapeDtypeStruct((4 * n,), jnp.float32,
                             sharding=NamedSharding(mesh, P("rank")))
    prog = coll._program_flat("allreduce", "ring", ReduceFunc.SUM,
                              "float8_e4m3fn", None, 128)
    text = _compiled_kernel(prog, x)
    assert "collective-permute" in text
