"""Scale tier: 16-rank daemon worlds and the 32-rank (8,4) 2D-mesh trees.

Reference bar: BASELINE config 4 is a 32-rank tree broadcast/scatter/
gather over a 2D ICI mesh, and the reference's orchestrator runs
multi-rank worlds as its core story (test/host/test_all.py:71-95). The
largest world anywhere in the round-2 corpus was 8; these tests pin
W=16 on both socket daemons, W=16 in the move-level property checker,
and W=32 on a virtual 32-device mesh (subprocess, the conftest cap is 8).
"""

import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from accl_tpu.testing import (connect_world, free_port_base, run_ranks,
                              sim_world)

W16 = 16


def _world16_suite(accls, quanta=0):
    """Representative collectives at W=16: fused allreduce (ring),
    allgather, rooted bcast, the barrier rendezvous, and compressed
    allreduce cells. ``quanta``: allowed error in representable-value
    steps for the compressed checks (0 = bitwise; the native daemon's
    independent C++ codecs get 1, as in test_compressed_sweep)."""
    n = 48
    ins = [np.linspace(r, r + 1, n, dtype=np.float32)
           for r in range(len(accls))]
    golden = sum(ins)

    def ar(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((n,), np.float32)
        a.allreduce(src, dst, n)
        dst.sync_from_device()
        return dst.data.copy()

    for out in run_ranks(accls, ar, timeout=120.0):
        np.testing.assert_allclose(out, golden, rtol=1e-5)

    def ag(a):
        src = a.buffer(data=ins[a.rank][:4])
        dst = a.buffer((4 * len(accls),), np.float32)
        a.allgather(src, dst, 4)
        dst.sync_from_device()
        return dst.data.copy()

    expect = np.concatenate([x[:4] for x in ins])
    for out in run_ranks(accls, ag, timeout=120.0):
        np.testing.assert_allclose(out, expect)

    def bc(a):
        buf = (a.buffer(data=ins[7]) if a.rank == 7
               else a.buffer((n,), np.float32))
        a.bcast(buf, n, root=7)
        buf.sync_from_device()
        return buf.data.copy()

    for out in run_ranks(accls, bc, timeout=120.0):
        np.testing.assert_allclose(out, ins[7])

    def bar(a):
        a.barrier()
        return True

    assert all(run_ranks(accls, bar, timeout=120.0))

    # Compressed fused ring allreduce at W=16, two cells against the
    # replayed-quantization goldens: per-hop ETH wire quantization across
    # the deep ring, and the mixed-flag substitution (bf16 src operands,
    # f32 result — phase 2 relays from the f32 dst)
    import ml_dtypes

    from test_compressed_sweep import _quant, _quantum, golden_allreduce

    cdtype = np.dtype(ml_dtypes.bfloat16)
    q = _quant(cdtype)
    small = [x[:16] for x in ins]

    def check(out, expect):
        if quanta == 0:
            np.testing.assert_array_equal(out, expect)
        else:
            err = np.abs(out - expect)
            tol = quanta * _quantum(expect, cdtype) + 1e-7
            assert (err <= tol).all(), err.max()

    def car_eth(a):
        src = a.buffer(data=small[a.rank])
        dst = a.buffer((16,), np.float32)
        a.allreduce(src, dst, 16, compress_dtype=cdtype)
        dst.sync_from_device()
        return dst.data.copy()

    expect = golden_allreduce(small, False, False, True, q)
    for r, out in enumerate(run_ranks(accls, car_eth, timeout=120.0)):
        check(out, expect[r])

    small_q = [q(v) for v in small]

    def car_mixed(a):
        src = a.buffer(data=small[a.rank].astype(cdtype))  # OP0 compressed
        dst = a.buffer((16,), np.float32)
        a.allreduce(src, dst, 16)
        dst.sync_from_device()
        return dst.data.copy()

    expect = golden_allreduce(small_q, True, False, False, q)
    for r, out in enumerate(run_ranks(accls, car_mixed, timeout=120.0)):
        check(out, expect[r])


def test_python_daemon_world16():
    accls = sim_world(W16, nbufs=32)
    try:
        _world16_suite(accls)
    finally:
        for a in accls:
            a.deinit()


def test_native_daemon_world16():
    binary = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native", "cclo_emud")
    if not os.path.exists(binary):
        pytest.skip("native daemon not built (make -C native)")
    port_base = free_port_base(span=2 * W16 + 8)
    procs = [subprocess.Popen(
        [binary, "--rank", str(r), "--world", str(W16),
         "--port-base", str(port_base), "--nbufs", "32"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(W16)]
    try:
        time.sleep(1.0)
        accls = connect_world(port_base, W16, timeout=60.0)
        _world16_suite(accls, quanta=1)
        for a in accls:
            a.deinit()
    finally:
        for p in procs:
            p.kill()


def test_native_daemon_world32_allreduce():
    """BASELINE config 4's rank count through the socket protocol: 32
    native daemon processes, fused ring allreduce."""
    binary = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native", "cclo_emud")
    if not os.path.exists(binary):
        pytest.skip("native daemon not built (make -C native)")
    W = 32
    port_base = free_port_base(span=2 * W + 8)
    procs = [subprocess.Popen(
        [binary, "--rank", str(r), "--world", str(W),
         "--port-base", str(port_base), "--nbufs", "64"],
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
        for r in range(W)]
    try:
        time.sleep(1.5)
        accls = connect_world(port_base, W, timeout=60.0)
        ins = [np.full(16, float(r), np.float32) for r in range(W)]

        def ar(a):
            src = a.buffer(data=ins[a.rank])
            dst = a.buffer((16,), np.float32)
            a.allreduce(src, dst, 16)
            dst.sync_from_device()
            return dst.data[0]

        res = run_ranks(accls, ar, timeout=180.0)
        assert all(v == sum(range(W)) for v in res)
        for a in accls:
            a.deinit()
    finally:
        for p in procs:
            p.kill()


def test_move_properties_world16():
    """The move-level executability checker at W=16 across the flag
    product for the fused ring ops (the tail-heavy schedules)."""
    import itertools

    from accl_tpu.constants import CCLOp, CollectiveAlgorithm
    from test_move_properties import build_world, run_world

    for op in (CCLOp.allreduce, CCLOp.allgather, CCLOp.reduce_scatter,
               CCLOp.gather, CCLOp.bcast):
        for c0, cr, eth in itertools.product((False, True), repeat=3):
            states = build_world(op, W16, 21, c0, False, cr, eth,
                                 seg_bytes=64, c_bytes=2, root=11,
                                 algorithm=CollectiveAlgorithm.AUTO)
            run_world(states, c_bytes=2)


_TREE32 = textwrap.dedent("""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.sharding import Mesh
    from accl_tpu.constants import ReduceFunc
    from accl_tpu.parallel.tree import Tree2DCollectives

    assert len(jax.devices()) == 32, len(jax.devices())
    mesh = Mesh(np.array(jax.devices()).reshape(8, 4), ("outer", "inner"))
    tc = Tree2DCollectives(mesh)
    W, n, root = 32, 16, 13
    rng = np.random.default_rng(0)
    ins = [rng.standard_normal(n).astype(np.float32) for _ in range(W)]

    x = tc.shard(ins)
    out = np.asarray(tc.bcast(x, root=root))
    for r in range(W):
        np.testing.assert_array_equal(out[r], ins[root])

    out = np.asarray(tc.reduce(x, root=root, func=ReduceFunc.SUM))
    np.testing.assert_allclose(out[root], sum(ins), rtol=1e-5)

    out = np.asarray(tc.allreduce(x))
    for r in range(W):
        np.testing.assert_allclose(out[r], sum(ins), rtol=1e-5)

    chunks = rng.standard_normal((W, W * n)).astype(np.float32)
    out = np.asarray(tc.scatter(tc.shard(list(chunks)), root=root))
    for r in range(W):
        np.testing.assert_array_equal(out[r],
                                      chunks[root, r * n:(r + 1) * n])

    out = np.asarray(tc.gather(x, root=root))
    np.testing.assert_array_equal(out[root], np.concatenate(ins))

    # the DRIVER tier at the same rank count: 32 ACCL ranks rendezvousing
    # over the 32-vdev mesh (allreduce + rooted bcast)
    from accl_tpu.device.tpu import tpu_world
    from accl_tpu.testing import run_ranks
    accls = tpu_world(32)
    def ar(a):
        src = a.buffer(data=np.full(8, 1.0 + a.rank, np.float32))
        dst = a.buffer((8,), np.float32)
        a.allreduce(src, dst, 8)
        dst.sync_from_device()
        return dst.data.copy()
    expect = sum(1.0 + r for r in range(32))
    assert all((o == expect).all()
               for o in run_ranks(accls, ar, timeout=300.0))
    def bc(a):
        buf = (a.buffer(data=ins[root]) if a.rank == root
               else a.buffer((n,), np.float32))
        a.bcast(buf, n, root=root)
        buf.sync_from_device()
        return buf.data.copy()
    for o in run_ranks(accls, bc, timeout=300.0):
        np.testing.assert_array_equal(o, ins[root])

    # wire-byte proportionality at (8,4): the flattened binomial
    # schedules must be byte-exact at W=32 too (permutes only, (W-1)
    # message copies for bcast, the static schedule sums for
    # scatter/gather) — the 2D analog of test_binomial_tree's checks
    from accl_tpu.parallel.tree import gather_rounds, scatter_rounds
    from accl_tpu.testing import hlo_permute_bytes as permute_bytes
    count, msg = 256, 256 * 4
    for op, bound in (
            ("bcast", (W - 1) * msg),
            ("scatter", sum(b * len(v)
                            for _s, b, v in scatter_rounds(W)) * msg),
            ("gather", sum(b * len(v)
                           for _s, b, v in gather_rounds(W)) * msg)):
        xo = tc.shard([np.zeros(W * count if op == "scatter" else count,
                                np.float32)] * W)
        hlo = tc._program(op, 0, ReduceFunc.SUM).lower(
            xo).compile().as_text()
        for banned in ("all-reduce", "all-gather", "reduce-scatter"):
            assert banned not in hlo, (op, banned)
        got = permute_bytes(hlo)
        assert 0 < got <= bound * 1.01, (op, got, bound)
    print("TREE32_OK")
""")


def test_tree2d_32rank_subprocess():
    """BASELINE config 4's shape: the (8,4) Tree2DCollectives suite on a
    32-device virtual mesh. Subprocess because the conftest pins this
    process to 8 virtual devices."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=32",
               JAX_PLATFORMS="")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", _TREE32], cwd=repo,
                         env=env, capture_output=True, text=True,
                         timeout=1200)  # covers the inner 300s run_ranks
                         # budgets so a wedged rank still reports output
    assert res.returncode == 0, res.stdout + res.stderr
    assert "TREE32_OK" in res.stdout
