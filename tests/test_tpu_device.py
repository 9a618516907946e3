"""The ACCL driver API on the TPU backend (virtual CPU mesh): the same
rank-parallel corpus that drives the emulator tier — the 3-tier test story.
"""

import numpy as np
import pytest

from accl_tpu import ACCLError, ErrorCode, ReduceFunc
from accl_tpu.device.tpu import tpu_world
from accl_tpu.testing import run_ranks

W = 8


def _data(count, dtype, seed):
    rng = np.random.default_rng(seed)
    if np.issubdtype(np.dtype(dtype), np.integer):
        return rng.integers(-50, 50, size=count).astype(dtype)
    return rng.standard_normal(count).astype(dtype)


@pytest.fixture(scope="module")
def world():
    return tpu_world(W, platform="cpu")


def test_allreduce(world):
    count = 100
    ins = [_data(count, np.float32, r) for r in range(W)]

    def fn(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((count,), np.float32)
        a.allreduce(src, dst, count)
        return dst.data.copy()

    golden = sum(ins)
    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, golden, rtol=1e-4, atol=1e-5)


def test_sendrecv(world):
    def fn(a):
        buf = a.buffer((16,), np.float32)
        if a.rank == 2:
            buf.data[:] = 42.0
            a.send(buf, 16, dst=5, tag=7)
        elif a.rank == 5:
            a.recv(buf, 16, src=2, tag=7)
            return buf.data.copy()
        return None

    res = run_ranks(world, fn)
    np.testing.assert_allclose(res[5], np.full(16, 42.0))


def test_send_completes_before_recv(world):
    def fn(a):
        buf = a.buffer((4,), np.float32)
        if a.rank == 0:
            buf.data[:] = 1.25
            a.send(buf, 4, dst=1, tag=0)  # completes eagerly
            return "sent"
        if a.rank == 1:
            import time
            time.sleep(0.1)
            a.recv(buf, 4, src=0, tag=0)
            return buf.data[0]
        return None

    res = run_ranks(world, fn)
    assert res[0] == "sent" and res[1] == 1.25


@pytest.mark.parametrize("root", [0, 3])
def test_bcast(world, root):
    count = 40
    golden = _data(count, np.float32, 77)

    def fn(a):
        buf = a.buffer((count,), np.float32)
        if a.rank == root:
            buf.data[:] = golden
        a.bcast(buf, count, root=root)
        return buf.data.copy()

    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, golden)


def test_scatter_gather_roundtrip(world):
    count = 8
    golden = _data(W * count, np.float32, 88)

    def fn(a):
        dst = a.buffer((count,), np.float32)
        if a.rank == 1:
            src = a.buffer(data=golden)
            a.scatter(src, dst, count, root=1)
            back = a.buffer((W * count,), np.float32)
            a.gather(dst, back, count, root=1)
            return back.data.copy()
        else:
            a.scatter(None, dst, count, root=1)
            a.gather(dst, None, count, root=1)
        return None

    res = run_ranks(world, fn)
    np.testing.assert_allclose(res[1], golden)


def test_reduce(world):
    count = 20
    ins = [_data(count, np.float32, 200 + r) for r in range(W)]

    def fn(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((count,), np.float32) if a.rank == 4 else None
        a.reduce(src, dst, count, root=4, func=ReduceFunc.SUM)
        return dst.data.copy() if dst is not None else None

    res = run_ranks(world, fn)
    np.testing.assert_allclose(res[4], sum(ins), rtol=1e-4, atol=1e-5)


def test_allgather_reduce_scatter(world):
    count = 4
    ins = [_data(W * count, np.float32, 300 + r) for r in range(W)]

    def fn(a):
        src = a.buffer(data=ins[a.rank])
        mine = a.buffer((count,), np.float32)
        a.reduce_scatter(src, mine, count)
        full = a.buffer((W * count,), np.float32)
        a.allgather(mine, full, count)
        return full.data.copy()

    golden = sum(ins)
    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, golden, rtol=1e-4, atol=1e-5)


def test_alltoall(world):
    count = 3
    ins = [_data(W * count, np.float32, 400 + r) for r in range(W)]

    def fn(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((W * count,), np.float32)
        a.alltoall(src, dst, count)
        return dst.data.copy()

    res = run_ranks(world, fn)
    for r in range(W):
        for s in range(W):
            np.testing.assert_allclose(
                res[r][s * count:(s + 1) * count],
                ins[s][r * count:(r + 1) * count])


def test_barrier_and_chaining(world):
    def fn(a):
        x = a.buffer(data=np.full(8, 2.0, np.float32))
        y = a.buffer((8,), np.float32)
        h = a.copy(x, y, run_async=True)
        a.barrier(waitfor=[h])
        return y.data[0]

    assert all(v == 2.0 for v in run_ranks(world, fn))


def test_wire_compressed_allreduce(world):
    count = 64
    ins = [_data(count, np.float32, 500 + r) for r in range(W)]

    def fn(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((count,), np.float32)
        a.allreduce(src, dst, count, compress_dtype=np.float16)
        return dst.data.copy()

    golden = sum(ins)
    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, golden, rtol=2e-2, atol=2e-2)


def test_recv_timeout(world):
    def fn(a):
        if a.rank == 6:
            a.set_timeout(0.3)
            buf = a.buffer((4,), np.float32)
            try:
                with pytest.raises(ACCLError) as ei:
                    a.recv(buf, 4, src=7, tag=99)
                assert ErrorCode.RECEIVE_TIMEOUT_ERROR in ei.value.errors
            finally:
                a.set_timeout(30.0)
        return None

    run_ranks(world, fn)


def test_recv_tag_any_matches_tagged_send(world):
    """TAG_ANY wildcard semantics must match the emulator tier."""
    def fn(a):
        buf = a.buffer((4,), np.float32)
        if a.rank == 0:
            buf.data[:] = 9.0
            a.send(buf, 4, dst=1, tag=5)
        elif a.rank == 1:
            a.recv(buf, 4, src=0)  # default TAG_ANY
            return buf.data[0]
        return None

    assert run_ranks(world, fn)[1] == 9.0


def test_sub_communicator_allreduce_tpu(world):
    """Split communicators execute over their own sub-mesh."""
    def fn(a):
        if a.rank in (2, 5, 7):
            sub = a.split_communicator([2, 5, 7])
            src = a.buffer(data=np.full(8, float(a.rank), np.float32))
            dst = a.buffer((8,), np.float32)
            a.allreduce(src, dst, 8, comm=sub)
            return dst.data[0]
        return None

    res = run_ranks(world, fn)
    assert res[2] == res[5] == res[7] == 14.0
    assert res[0] is None


def test_concurrent_world_subcomm_and_p2p(world):
    """World + disjoint sub-communicator collectives and p2p in flight
    simultaneously: the rendezvous keys on (comm, op_index), so the
    three traffic streams must never cross-match."""
    W = len(world)
    half = W // 2

    def fn(a):
        r = a.rank
        sub = a.split_communicator(list(range(half)) if r < half
                                   else list(range(half, W)))
        for it in range(8):
            n = 32
            d = a.buffer((n,), np.float32)
            h1 = a.allreduce(a.buffer(data=np.full(n, r + 1.0, np.float32)),
                             d, n, run_async=True)
            d2 = a.buffer((n,), np.float32)
            h2 = a.allreduce(a.buffer(data=np.full(n, 10.0 + r, np.float32)),
                             d2, n, comm=sub, run_async=True)
            dst = a.buffer((n,), np.float32)
            hs = a.send(a.buffer(data=np.full(n, 100.0 + r, np.float32)),
                        n, dst=(r + 1) % W, tag=it, run_async=True)
            hr = a.recv(dst, n, src=(r - 1) % W, tag=it, run_async=True)
            for h in (h1, h2, hs, hr):
                h.wait(60)
            assert d.data[0] == W * (W + 1) / 2, (r, it, d.data[0])
            lo = 0 if r < half else half
            assert d2.data[0] == sum(10.0 + x for x in range(lo, lo + half))
            assert dst.data[0] == 100.0 + (r - 1) % W
        return True

    assert all(run_ranks(world, fn, timeout=120.0))


def test_recv_count_mismatch_error(world):
    """Short send into a longer recv must fail like the emulator tier."""
    def fn(a):
        if a.rank == 3:
            buf = a.buffer((4,), np.float32)
            a.send(buf, 4, dst=4, tag=11)
        elif a.rank == 4:
            dst = a.buffer((8,), np.float32)
            with pytest.raises(ACCLError) as ei:
                a.recv(dst, 8, src=3, tag=11)
            assert ErrorCode.DMA_MISMATCH_ERROR in ei.value.errors
        return None

    run_ranks(world, fn)


def test_disjoint_comms_execute_concurrently():
    """Two split communicators must make progress simultaneously: comm A's
    collective is artificially blocked mid-execution, and comm B's
    collective must still complete — proving the rendezvous lock is not
    held during execution (it used to serialize every communicator of the
    world through one lock, including jit/dispatch time)."""
    import threading

    import numpy as np

    from jax.sharding import Mesh
    from accl_tpu.parallel.collectives import MeshCollectives

    accls = tpu_world(4, platform="cpu")
    ctx = accls[0].device.ctx
    started = threading.Event()
    release = threading.Event()
    b_done = threading.Barrier(2)
    sync = threading.Barrier(4)

    class SlowColl:
        """Delegating wrapper that parks comm A inside _launch."""

        def __init__(self, inner):
            self._inner = inner

        def __getattr__(self, name):   # the launch's plan store, etc.
            return getattr(self._inner, name)

        def _program_flat(self, *args):    # the one launch's program
            prog = self._inner._program_flat(*args)

            def parked(x):
                started.set()
                assert release.wait(30), "comm B never released comm A"
                return prog(x)

            return parked

    def fn(a):
        if a.rank in (0, 1):
            sub = a.split_communicator([0, 1])
            if a.rank == 0:
                devs = list(np.asarray(ctx.mesh.devices).reshape(-1))[:2]
                inner = MeshCollectives(
                    Mesh(np.asarray(devs), (ctx.axis_name,)), ctx.axis_name)
                ctx._subcolls[sub.comm_id] = SlowColl(inner)
            sync.wait()
            src = a.buffer(data=np.full(8, 1.0 + a.rank, np.float32))
            dst = a.buffer((8,), np.float32)
            a.allreduce(src, dst, 8, comm=sub)
            return dst.data[0]
        sub = a.split_communicator([2, 3])
        sync.wait()
        assert started.wait(30), "comm A never reached execution"
        # comm A is parked inside its collective right now; comm B's
        # collective must complete anyway
        src = a.buffer(data=np.full(8, 1.0 + a.rank, np.float32))
        dst = a.buffer((8,), np.float32)
        a.allreduce(src, dst, 8, comm=sub)
        b_done.wait()
        if a.rank == 2:
            release.set()
        return dst.data[0]

    res = run_ranks(accls, fn)
    assert res[0] == res[1] == 3.0   # 1 + 2
    assert res[2] == res[3] == 7.0   # 3 + 4


def test_waiter_survives_slow_execution():
    """A rank whose rendezvous timeout expires while the collective is
    already executing must wait for the publication instead of returning a
    bogus RECEIVE_TIMEOUT (which would also leak an undrainable result
    entry and desync the per-comm call stream)."""
    import time

    accls = tpu_world(2, platform="cpu", timeout=0.6)
    ctx = accls[0].device.ctx
    real = ctx.coll

    ran = []

    class Slow:
        def __getattr__(self, name):
            return getattr(real, name)

        def _program_flat(self, *args):    # the one launch's program
            prog = real._program_flat(*args)

            def slow(x):
                ran.append(1)
                time.sleep(1.5)      # longer than the rendezvous timeout
                return prog(x)

            return slow

    ctx.coll = Slow()
    try:
        def fn(a):
            src = a.buffer(data=np.full(4, 1.0 + a.rank, np.float32))
            dst = a.buffer((4,), np.float32)
            h = a.allreduce(src, dst, 4, run_async=True)
            h.wait(10)               # user-level wait outlives the stall
            return dst.data[0]

        res = run_ranks(accls, fn)
        assert ran                # the launch did run the slow program
        assert res == [3.0, 3.0]
        assert not ctx._pending  # no leaked rendezvous state
    finally:
        ctx.coll = real


def _pop_flat(coll, ops, root) -> set:
    """Drop the world's flat programs of ``ops`` at ``root`` and return
    their keys: a launch that needs one again must rebuild it."""
    keys = {k for k in list(coll._cache)
            if k[0] == "flat" and k[1] in ops and k[5] == root}
    for k in keys:
        coll._cache.pop(k, None)
    return keys


def test_rooted_collectives_use_2d_tree(world):
    """Rooted ops on host mirrors run the flat programs their
    device-resident twins run — no 2D tree, even where W=8 folds to
    (2, 4): the world's program cache gains the ``"flat"`` program of
    each of bcast/scatter/gather/reduce, and the results are right."""
    ctx = world[0].device.ctx
    coll = ctx.coll
    assert not hasattr(ctx, "tree")
    count, root = 12, 5
    rooted = {"bcast", "scatter", "gather", "reduce"}
    _pop_flat(coll, rooted, root)
    x = _data(count, np.float32, 99)
    chunks = _data(W * count, np.float32, 98)
    ins = [_data(count, np.float32, 90 + r) for r in range(W)]

    def fn(a):
        buf = a.buffer(data=x) if a.rank == root else a.buffer(
            (count,), np.float32)
        a.bcast(buf, count, root=root)
        out_b = buf.data.copy()

        src = a.buffer(data=chunks) if a.rank == root else None
        dst = a.buffer((count,), np.float32)
        a.scatter(src, dst, count, root=root)
        out_s = dst.data.copy()

        gsrc = a.buffer(data=ins[a.rank])
        gdst = a.buffer((W * count,), np.float32) if a.rank == root else None
        a.gather(gsrc, gdst, count, root=root)
        out_g = gdst.data.copy() if gdst is not None else None

        rsrc = a.buffer(data=ins[a.rank])
        rdst = a.buffer((count,), np.float32) if a.rank == root else None
        a.reduce(rsrc, rdst, count, root=root)
        out_r = rdst.data.copy() if rdst is not None else None
        return out_b, out_s, out_g, out_r

    res = run_ranks(world, fn)
    for r in range(W):
        np.testing.assert_allclose(res[r][0], x)
        np.testing.assert_allclose(res[r][1],
                                   chunks[r * count:(r + 1) * count])
    np.testing.assert_allclose(res[root][2], np.concatenate(ins))
    np.testing.assert_allclose(res[root][3], sum(ins), rtol=1e-5)
    assert {k[1] for k in _pop_flat(coll, rooted, root)} == rooted

    # an ETH-compressed reduce runs the flat program of its wire: the
    # decompress-before-arith wire numerics are the contract
    def fc(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((count,), np.float32) if a.rank == root else None
        a.reduce(src, dst, count, root=root, compress_dtype=np.float16)
        return dst.data.copy() if dst is not None else None

    out = run_ranks(world, fc)[root]
    np.testing.assert_allclose(out, sum(ins), atol=0.05)
    assert {k[4] for k in _pop_flat(coll, {"reduce"}, root)} == {"float16"}


@pytest.mark.parametrize("op", ["bcast", "scatter", "gather", "reduce"])
def test_rooted_op_same_bits_on_every_placement(world, op):
    """On the 8-rank world (a 2x4 factorization, where a 2D tree once
    served host mirrors) a rooted op leaves the same bits whether every
    buffer is device-resident, every buffer a host mirror, or the group
    mixes the two: all three run one flat program."""
    import jax

    count, root = 12, 3
    ins = [_data(W * count, np.float32, 700 + r) for r in range(W)]

    def run(on_device):
        def fn(a):
            resident = on_device(a.rank)
            x = ins[a.rank]

            def buf(n, data=None):
                if data is None:
                    return a.buffer((n,), np.float32,
                                    device_resident=resident)
                if resident:
                    data = jax.device_put(data, a.device.my_device)
                return a.buffer(data=data)

            mine = a.rank == root
            if op == "bcast":
                out = buf(count, x[:count])
                a.bcast(out, count, root=root)
            elif op == "scatter":
                out = buf(count)
                a.scatter(buf(W * count, x) if mine else None, out, count,
                          root=root)
            elif op == "gather":
                out = buf(W * count) if mine else None
                a.gather(buf(count, x[:count]), out, count, root=root)
            else:
                out = buf(count) if mine else None
                a.reduce(buf(count, x[:count]), out, count, root=root)
            return None if out is None else out.data.copy()

        return run_ranks(world, fn)

    device = run(lambda r: True)
    host = run(lambda r: False)
    mixed = run(lambda r: r % 2 == 0)
    for r in range(W):
        if device[r] is None:
            assert host[r] is None and mixed[r] is None
            continue
        assert device[r].tobytes() == host[r].tobytes(), r
        assert device[r].tobytes() == mixed[r].tobytes(), r
    rows = [x[:count] for x in ins]
    want = {"bcast": [rows[root]] * W,
            "scatter": [ins[root][r * count:(r + 1) * count]
                        for r in range(W)],
            "gather": {root: np.concatenate(rows)},
            "reduce": {root: sum(rows)}}[op]
    for r, w in (enumerate(want) if isinstance(want, list)
                 else want.items()):
        np.testing.assert_allclose(device[r], w, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("op", ["allreduce", "reduce"])
def test_64bit_collective_refused_not_truncated(world, op):
    """With x64 off jax holds int64 in 32 bits, so an int64 collective on
    host mirrors fails with INVALID_CALL before any payload reaches a
    device, and leaves its destinations as they were (it used to return
    a truncated sum)."""
    import jax

    if jax.config.jax_enable_x64:
        pytest.skip("x64 enabled: int64 is canonical")
    root = 0

    def fn(a):
        src = a.buffer(data=np.full(4, 2**40 + a.rank, np.int64))
        dst = (a.buffer(data=np.full(4, -7, np.int64))
               if op == "allreduce" or a.rank == root else None)
        with pytest.raises(ACCLError) as ei:
            if op == "allreduce":
                a.allreduce(src, dst, 4)
            else:
                a.reduce(src, dst, 4, root=root)
        assert ErrorCode.INVALID_CALL in ei.value.errors
        return None if dst is None else dst.data.copy()

    for out in run_ranks(world, fn):
        if out is not None:
            np.testing.assert_array_equal(out, np.full(4, -7, np.int64))


def test_wire_compressed_rooted_ops_match_emulator_tier(world):
    """ETH-compressed bcast/scatter/gather must apply the same lossy wire
    quantization as the emulator tier (payloads that crossed the wire are
    fp16-quantized; the root's own data is not) — bitwise cross-tier
    agreement on identical inputs."""
    from accl_tpu.testing import emu_world

    count, root = 16, 2
    x = _data(W * count, np.float32, 55)
    ins = [_data(count, np.float32, 60 + r) for r in range(W)]

    def fn(a):
        buf = (a.buffer(data=x[:count]) if a.rank == root
               else a.buffer((count,), np.float32))
        a.bcast(buf, count, root=root, compress_dtype=np.float16)
        out_b = buf.data.copy()

        src = a.buffer(data=x) if a.rank == root else None
        dst = a.buffer((count,), np.float32)
        a.scatter(src, dst, count, root=root, compress_dtype=np.float16)
        out_s = dst.data.copy()

        gsrc = a.buffer(data=ins[a.rank])
        gdst = a.buffer((W * count,), np.float32) if a.rank == root else None
        a.gather(gsrc, gdst, count, root=root, compress_dtype=np.float16)
        out_g = gdst.data.copy() if gdst is not None else None

        # per-rank-distinct data so the self-chunk restore index is strict
        asrc = a.buffer(data=_data(W * count, np.float32, 70 + a.rank))
        adst = a.buffer((W * count,), np.float32)
        a.alltoall(asrc, adst, count, compress_dtype=np.float16)
        return out_b, out_s, out_g, adst.data.copy()

    tpu_res = run_ranks(world, fn)
    emu = emu_world(W)
    try:
        emu_res = run_ranks(emu, fn)
    finally:
        for a in emu:
            a.deinit()
    for r in range(W):
        np.testing.assert_array_equal(tpu_res[r][0], emu_res[r][0],
                                      err_msg=f"bcast rank {r}")
        np.testing.assert_array_equal(tpu_res[r][1], emu_res[r][1],
                                      err_msg=f"scatter rank {r}")
        np.testing.assert_array_equal(tpu_res[r][3], emu_res[r][3],
                                      err_msg=f"alltoall rank {r}")
    np.testing.assert_array_equal(tpu_res[root][2], emu_res[root][2],
                                  err_msg="gather root")


def test_bcast_round_robin_selector_skips_tree(world):
    """An explicit ROUND_ROBIN selector on host mirrors runs the flat
    binomial bcast, the program AUTO and the device-resident twin run
    (algorithm parity with the move engine): no tree exists to skip."""
    ctx = world[0].device.ctx
    assert not hasattr(ctx, "tree")
    _pop_flat(ctx.coll, {"bcast"}, 0)
    x = _data(6, np.float32, 77)

    def fn(a):
        buf = a.buffer(data=x) if a.rank == 0 else a.buffer((6,), np.float32)
        a.bcast(buf, 6, root=0, algorithm="round_robin")
        return buf.data.copy()

    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, x)
    assert {k[:3] for k in _pop_flat(ctx.coll, {"bcast"}, 0)} == {
        ("flat", "bcast", "xla")}


def test_tpu_world_real_chip():
    """Hardware tier: the driver API on the REAL TPU device (single-rank
    world). Gated on ACCL_TEST_TPU=1 with a tpu backend — CHANGES.md
    (PR 21) records the last on-chip pass. Reference bar: the
    hardware-tier tests (test/host/test_tcp_cmac_seq_mpi.py:29-443)."""
    import os

    import jax

    if not os.environ.get("ACCL_TEST_TPU"):
        pytest.skip("set ACCL_TEST_TPU=1 to run against the real chip")
    if jax.default_backend() != "tpu":
        pytest.skip("no tpu backend available")
    accls = tpu_world(1)
    a = accls[0]
    src = a.buffer(data=np.arange(64, dtype=np.float32))
    dst = a.buffer((64,), np.float32)
    a.allreduce(src, dst, 64)
    dst.sync_from_device()
    np.testing.assert_allclose(dst.data, np.arange(64))
    x = a.buffer(data=np.full(32, 2.0, np.float32))
    y = a.buffer(data=np.full(32, 3.0, np.float32))
    z = a.buffer((32,), np.float32)
    a.combine(32, ReduceFunc.SUM, x, y, z)
    z.sync_from_device()
    np.testing.assert_allclose(z.data, 5.0)
