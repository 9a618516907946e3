"""Device-resident buffers (the reference's ``to_from_fpga=False`` fast
path, test/host/test_tcp_cmac_seq_mpi.py:29-443) and the device-fabric
send/recv path on the TPU tier.

Covers: zero-staging dense collectives, fallback interop with host-mirror
buffers, send/recv riding the ppermute exchange program (payload lives on
device end to end, HLO contains collective-permute), rejection on
backends without device arrays, and the collective deadline sweeper.
"""

import numpy as np
import pytest

import jax

from accl_tpu import ACCLError, ErrorCode, ReduceFunc
from accl_tpu.device.tpu import tpu_world
from accl_tpu.testing import run_ranks

W = 8


def _data(count, seed):
    return np.random.default_rng(seed).standard_normal(count).astype(
        np.float32)


@pytest.fixture(scope="module")
def world():
    return tpu_world(W, platform="cpu")


def _dev_src(a, arr):
    return a.buffer(data=jax.device_put(arr, a.device.my_device))


def test_buffer_modes(world):
    a = world[0]
    host = a.buffer((8,), np.float32)
    assert not host.is_device_resident
    dev = a.buffer((8,), np.float32, device_resident=True)
    assert dev.is_device_resident
    assert dev.shape == (8,) and dev.dtype == np.dtype(np.float32)
    np.testing.assert_array_equal(dev.data, np.zeros(8, np.float32))
    with pytest.raises(ValueError):
        host.jax
    with pytest.raises(ValueError):
        dev[2:4]  # no sub-buffer views on device arrays


def test_adopt_rejected_on_emulator_backend():
    from accl_tpu.testing import emu_world
    accls = emu_world(2)
    try:
        with pytest.raises(ValueError, match="device-array storage"):
            accls[0].buffer((4,), np.float32, device_resident=True)
        with pytest.raises(ValueError, match="device-array storage"):
            accls[0].buffer(data=jax.numpy.zeros(4))
    finally:
        for a in accls:
            a.deinit()


def test_adopt_rejects_sharded_arrays(world):
    from jax.sharding import NamedSharding, PartitionSpec as P
    ctx = world[0].device.ctx
    sharded = jax.device_put(
        np.zeros((W, 4), np.float32),
        NamedSharding(ctx.mesh, P(ctx.axis_name)))
    with pytest.raises(ValueError, match="single-device"):
        world[0].buffer(data=sharded)


@pytest.mark.parametrize("count", [64, 1000])
def test_allreduce_device_resident(world, count):
    ins = [_data(count, 10 + r) for r in range(W)]

    def fn(a):
        src = _dev_src(a, ins[a.rank])
        dst = a.buffer((count,), np.float32, device_resident=True)
        a.allreduce(src, dst, count)
        assert dst.is_device_resident  # result stayed on device
        return dst.data.copy()

    golden = sum(ins)
    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, golden, rtol=1e-4, atol=1e-5)


def test_allgather_reduce_scatter_alltoall_device_resident(world):
    count = 48
    ins = [_data(count, 30 + r) for r in range(W)]
    wide = [_data(W * count, 60 + r) for r in range(W)]

    def fn(a):
        r = a.rank
        # allgather
        src = _dev_src(a, ins[r])
        dst = a.buffer((W * count,), np.float32, device_resident=True)
        a.allgather(src, dst, count)
        ag = dst.data.copy()
        # reduce_scatter
        src2 = _dev_src(a, wide[r])
        dst2 = a.buffer((count,), np.float32, device_resident=True)
        a.reduce_scatter(src2, dst2, count)
        rs = dst2.data.copy()
        # alltoall
        src3 = _dev_src(a, wide[r])
        dst3 = a.buffer((W * count,), np.float32, device_resident=True)
        a.alltoall(src3, dst3, count)
        return ag, rs, dst3.data.copy()

    res = run_ranks(world, fn)
    gold_ag = np.concatenate(ins)
    gold_sum = sum(wide)
    for r, (ag, rs, a2a) in enumerate(res):
        np.testing.assert_allclose(ag, gold_ag, rtol=1e-5)
        np.testing.assert_allclose(
            rs, gold_sum[r * count:(r + 1) * count], rtol=1e-4, atol=1e-5)
        gold_a2a = np.concatenate(
            [wide[s][r * count:(r + 1) * count] for s in range(W)])
        np.testing.assert_allclose(a2a, gold_a2a, rtol=1e-5)


def test_mixed_worlds_fall_back(world):
    """Some ranks device-resident, some host-mirror: the launch falls back
    to staged execution and every rank still gets the right answer."""
    count = 32
    ins = [_data(count, 90 + r) for r in range(W)]

    def fn(a):
        if a.rank % 2 == 0:
            src = _dev_src(a, ins[a.rank])
            dst = a.buffer((count,), np.float32, device_resident=True)
        else:
            src = a.buffer(data=ins[a.rank])
            dst = a.buffer((count,), np.float32)
        a.allreduce(src, dst, count)
        return dst.data.copy()

    golden = sum(ins)
    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, golden, rtol=1e-4, atol=1e-5)


def test_rooted_ops_on_device_buffers(world):
    """bcast/gather aren't on the zero-staging path yet; device-resident
    operands must still work through the staged fallback."""
    count = 16
    payload = _data(count, 7)

    def fn(a):
        buf = (_dev_src(a, payload) if a.rank == 3
               else a.buffer((count,), np.float32, device_resident=True))
        a.bcast(buf, count, root=3)
        return buf.data.copy()

    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, payload, rtol=1e-6)


def test_wire_compressed_allreduce_device_matches_host(world):
    """ETH (wire) compression stays eligible for the zero-staging path —
    and its numerics must match the host-staged tier exactly."""
    count = 128
    ins = [_data(count, 40 + r) for r in range(W)]

    def fn_dev(a):
        src = _dev_src(a, ins[a.rank])
        dst = a.buffer((count,), np.float32, device_resident=True)
        a.allreduce(src, dst, count, compress_dtype=np.float16)
        return dst.data.copy()

    def fn_host(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((count,), np.float32)
        a.allreduce(src, dst, count, compress_dtype=np.float16)
        return dst.data.copy()

    dev_res = run_ranks(world, fn_dev)
    host_res = run_ranks(world, fn_host)
    for d, h in zip(dev_res, host_res):
        np.testing.assert_array_equal(d, h)


# ---------------------------------------------------------------------------
# send/recv through the device fabric
# ---------------------------------------------------------------------------

def test_send_snapshot_is_device_array(world):
    """The host snapshot path is gone: a parked send payload is a
    jax.Array living on the sender's device."""
    ctx = world[0].device.ctx

    def fn(a):
        if a.rank == 1:
            buf = a.buffer(data=np.full(8, 5.0, np.float32))
            a.send(buf, 8, dst=6, tag=3)
            # parked payload: device array on MY device
            key = [k for k in ctx._sends if k[1] == 1]
            assert key, "send not parked"
            _tag, payload = ctx._sends[key[0]][0]
            assert isinstance(payload, jax.Array)
            assert payload.device == a.device.my_device
        elif a.rank == 6:
            buf = a.buffer((8,), np.float32)
            a.recv(buf, 8, src=1, tag=3)
            return buf.data.copy()
        return None

    res = run_ranks(world, fn)
    np.testing.assert_allclose(res[6], np.full(8, 5.0))


def test_exchange_program_contains_collective_permute(world):
    """The transfer rides the mesh program: the lowered exchange HLO
    contains a collective-permute op."""
    ctx = world[0].device.ctx
    coll = ctx.coll
    prog = coll._sendrecv_program_flat(((1, 6),))
    x = jax.device_put(
        np.zeros((W * 8,), np.float32), coll.flat_sharding)
    lowered = prog.lower(x)
    texts = [lowered.as_text(), lowered.compile().as_text()]
    assert any("collective_permute" in t or "collective-permute" in t
               or "CollectivePermute" in t for t in texts)


def test_recv_uses_exchange_transfer(world, monkeypatch):
    """A matched recv moves the payload via TpuContext.exchange_transfer
    (the ppermute program), not a host memcpy."""
    ctx = world[0].device.ctx
    calls = []
    orig = type(ctx).exchange_transfer

    def spy(self, comm, payload, src_local, dst_local):
        calls.append((src_local, dst_local))
        return orig(self, comm, payload, src_local, dst_local)

    monkeypatch.setattr(type(ctx), "exchange_transfer", spy)

    def fn(a):
        if a.rank == 2:
            buf = a.buffer(data=np.arange(16, dtype=np.float32))
            a.send(buf, 16, dst=5, tag=9)
        elif a.rank == 5:
            buf = a.buffer((16,), np.float32)
            a.recv(buf, 16, src=2, tag=9)
            return buf.data.copy()
        return None

    res = run_ranks(world, fn)
    np.testing.assert_allclose(res[5], np.arange(16, dtype=np.float32))
    assert (2, 5) in calls


def test_sendrecv_device_resident_end_to_end(world):
    """Device-resident src and dst: the payload never leaves the device
    (zero-copy snapshot; result is a rebind of the exchange output)."""
    count = 32
    payload = _data(count, 55)

    def fn(a):
        if a.rank == 0:
            src = _dev_src(a, payload)
            a.send(src, count, dst=7, tag=1)
        elif a.rank == 7:
            dst = a.buffer((count,), np.float32, device_resident=True)
            a.recv(dst, count, src=0, tag=1)
            assert dst.is_device_resident
            return dst.data.copy()
        return None

    res = run_ranks(world, fn)
    np.testing.assert_allclose(res[7], payload, rtol=1e-6)


def test_run_async_submission_does_not_block_on_launch():
    """call_async with run_async=True must return before the collective
    executes, even for the group-completing rank — the heavy launch hops
    to the worker thread (async contract)."""
    import threading
    import time
    accls = tpu_world(2, platform="cpu")
    ctx = accls[0].device.ctx
    real = ctx.coll
    release = threading.Event()

    parked = threading.Event()

    class Slow:
        def __getattr__(self, name):
            return getattr(real, name)

        def _program_flat(self, *args):    # the one launch's program
            prog = real._program_flat(*args)

            def slow(x):
                parked.set()
                assert release.wait(10), "launch never released"
                return prog(x)

            return slow

    ctx.coll = Slow()
    try:
        bufs = []
        for a in accls:
            src = a.buffer(data=np.ones(4, np.float32))
            dst = a.buffer((4,), np.float32)
            bufs.append((src, dst))
        t0 = time.monotonic()
        handles = [a.allreduce(src, dst, 4, run_async=True)
                   for a, (src, dst) in zip(accls, bufs)]
        submit_elapsed = time.monotonic() - t0
        # submissions returned while the launch is still parked
        assert submit_elapsed < 5.0
        assert not handles[1].done()
        assert parked.wait(10), "the launch never reached its program"
        assert not handles[1].done()
        release.set()
        for h in handles:
            h.wait(10)
    finally:
        ctx.coll = real


def test_collective_group_timeout_via_sweeper():
    """A collective whose peers never arrive fails with
    RECEIVE_TIMEOUT_ERROR (enforced by the context's deadline sweeper —
    no waiter thread is parked per member anymore)."""
    import time
    accls = tpu_world(2, platform="cpu", timeout=0.4)
    a = accls[0]
    src = a.buffer(data=np.ones(4, np.float32))
    dst = a.buffer((4,), np.float32)
    t0 = time.monotonic()
    h = a.allreduce(src, dst, 4, run_async=True)
    with pytest.raises(ACCLError) as ei:
        h.wait(5.0)
    elapsed = time.monotonic() - t0
    assert ei.value.error_word & int(ErrorCode.RECEIVE_TIMEOUT_ERROR)
    assert elapsed < 3.0  # deadline + sweeper slack, not the wait budget
    assert not a.device.ctx._pending


# -- rooted ops on the fast path (to_from_fpga=False applies to EVERY op,
#    reference test_tcp_cmac_seq_mpi.py:29-443) ---------------------------

def _host_staging_spy(world, monkeypatch):
    """Count host-staging crossings (operand reads / result writes) on
    every rank's device: the rooted device-resident fast path must make
    ZERO of either."""
    from accl_tpu.device.tpu import TpuDevice
    crossings = []
    orig_read = TpuDevice._read_operand
    orig_write = TpuDevice._write_result

    def spy_read(self, *a, **k):
        crossings.append("read")
        return orig_read(self, *a, **k)

    def spy_write(self, *a, **k):
        crossings.append("write")
        return orig_write(self, *a, **k)

    monkeypatch.setattr(TpuDevice, "_read_operand", spy_read)
    monkeypatch.setattr(TpuDevice, "_write_result", spy_write)
    return crossings


def test_bcast_device_resident_zero_host_copy(world, monkeypatch):
    count = 48
    payload = _data(count, 70)
    crossings = _host_staging_spy(world, monkeypatch)

    def fn(a):
        init = payload if a.rank == 3 else np.zeros(count, np.float32)
        buf = _dev_src(a, init)
        a.bcast(buf, count, root=3)
        assert buf.is_device_resident
        return buf.data.copy()

    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, payload, rtol=1e-6)
    assert not crossings, f"host staging on fast path: {crossings}"


def test_scatter_device_resident_zero_host_copy(world, monkeypatch):
    count = 32
    flat = _data(W * count, 71)
    crossings = _host_staging_spy(world, monkeypatch)

    def fn(a):
        src = _dev_src(a, flat) if a.rank == 2 else None
        dst = a.buffer((count,), np.float32, device_resident=True)
        a.scatter(src, dst, count, root=2)
        assert dst.is_device_resident
        return dst.data.copy()

    outs = run_ranks(world, fn)
    for r, out in enumerate(outs):
        np.testing.assert_allclose(out, flat[r * count:(r + 1) * count],
                                   rtol=1e-6)
    assert not crossings, f"host staging on fast path: {crossings}"


def test_gather_device_resident_zero_host_copy(world, monkeypatch):
    count = 24
    ins = [_data(count, 80 + r) for r in range(W)]
    crossings = _host_staging_spy(world, monkeypatch)

    def fn(a):
        src = _dev_src(a, ins[a.rank])
        dst = (a.buffer((W * count,), np.float32, device_resident=True)
               if a.rank == 5 else None)
        a.gather(src, dst, count, root=5)
        if a.rank == 5:
            assert dst.is_device_resident
            return dst.data.copy()
        return None

    outs = run_ranks(world, fn)
    np.testing.assert_allclose(outs[5], np.concatenate(ins), rtol=1e-6)
    assert not crossings, f"host staging on fast path: {crossings}"


@pytest.mark.parametrize("func", [ReduceFunc.SUM, ReduceFunc.MAX])
def test_reduce_device_resident_zero_host_copy(world, monkeypatch, func):
    count = 40
    ins = [_data(count, 90 + r) for r in range(W)]
    crossings = _host_staging_spy(world, monkeypatch)

    def fn(a):
        src = _dev_src(a, ins[a.rank])
        dst = (a.buffer((count,), np.float32, device_resident=True)
               if a.rank == 0 else None)
        a.reduce(src, dst, count, root=0, func=func)
        if a.rank == 0:
            return dst.data.copy()
        return None

    outs = run_ranks(world, fn)
    golden = (sum(ins) if func == ReduceFunc.SUM
              else np.maximum.reduce(ins))
    np.testing.assert_allclose(outs[0], golden, rtol=1e-4, atol=1e-5)
    assert not crossings, f"host staging on fast path: {crossings}"


def test_rooted_mixed_residency_falls_back(world):
    """A host-mirror buffer anywhere in the group disqualifies the fast
    path; the staged path must still produce the right answer."""
    count = 16
    payload = _data(count, 99)

    def fn(a):
        if a.rank == 0:  # root stays host-resident -> fallback
            buf = a.buffer(data=payload)
        else:
            buf = _dev_src(a, np.zeros(count, np.float32))
        a.bcast(buf, count, root=0)
        return buf.data.copy()

    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, payload, rtol=1e-6)


def test_compressed_rooted_rides_fast_path(world, monkeypatch):
    """ETH-compressed rooted ops on device-resident buffers take the
    zero-staging fast path too — the wire cast rides INSIDE the binomial
    program (cast per hop, idempotent), and the numerics still match the
    emulator tier's contract: root exact, receivers quantized once."""
    count = 64
    payload = _data(count, 101)
    crossings = _host_staging_spy(world, monkeypatch)

    def fn(a):
        init = payload if a.rank == 1 else np.zeros(count, np.float32)
        buf = _dev_src(a, init)
        a.bcast(buf, count, root=1, compress_dtype=np.float16)
        return buf.data.copy()

    outs = run_ranks(world, fn)
    np.testing.assert_allclose(outs[1], payload, rtol=1e-6)  # root exact
    for r in (0, 2):  # others quantized through the fp16 wire
        np.testing.assert_allclose(
            outs[r], payload.astype(np.float16).astype(np.float32),
            rtol=1e-6)
    assert not crossings, f"host staging on fast path: {crossings}"


def test_concurrent_sendrecv_batches_exchange_programs(world, monkeypatch):
    """K concurrently-matched p2p transfers must ride <=2 exchange
    programs (opportunistic window batching), not one full-mesh program
    per pair. The spy slows each program slightly so arrivals during the
    first program deterministically pile into the second."""
    import time as _time

    from accl_tpu.parallel.collectives import MeshCollectives

    calls = []
    orig = MeshCollectives.exchange_flat
    ctx = world[0].device.ctx

    def spy(self, x, pairs):
        calls.append(tuple(pairs))
        # deterministic window: hold this program until every other
        # transfer is queued behind it (bounded), so scheduling stalls
        # on a loaded machine cannot split the batch into >2 programs
        deadline = _time.monotonic() + 10.0
        while _time.monotonic() < deadline:
            with ctx._lock:
                queued = sum(len(v) for v in ctx._xchg_pending.values())
            done_pairs = sum(len(p) for p in calls)
            if done_pairs + queued >= W:
                break
            _time.sleep(0.002)
        return orig(self, x, pairs)

    monkeypatch.setattr(MeshCollectives, "exchange_flat", spy)
    count = 16
    ins = [_data(count, 200 + r) for r in range(W)]

    def fn(a):
        # ring shift: rank r sends to r+1, receives from r-1 — W matched
        # pairs with distinct sources and destinations
        peer_to = (a.rank + 1) % W
        peer_from = (a.rank - 1) % W
        src = _dev_src(a, ins[a.rank])
        dst = a.buffer((count,), np.float32, device_resident=True)
        h = a.send(src, count, dst=peer_to, tag=3, run_async=True)
        a.recv(dst, count, src=peer_from, tag=3)
        h.wait()
        return dst.data.copy()

    outs = run_ranks(world, fn)
    for r, out in enumerate(outs):
        np.testing.assert_allclose(out, ins[(r - 1) % W], rtol=1e-6)
    assert len(calls) <= 2, (
        f"{W} concurrent transfers ran {len(calls)} exchange programs: "
        f"{calls}")
    # every pair crossed in SOME program
    moved = {p for ps in calls for p in ps}
    assert moved == {((r - 1) % W, r) for r in range(W)}


def test_device_resident_storm_all_ops(world):
    """Back-to-back device-resident collectives across every op family
    (dense fast path AND the rooted tree programs) with varying counts
    and rotating roots: results stay correct, and a result buffer REUSED
    as the next iteration's source keeps its residency."""
    def fn(a):
        r = a.rank
        prev = None  # previous allreduce dest, reused as the next source
        for it in range(14):
            op = ["allreduce", "bcast", "scatter", "gather", "reduce",
                  "allgather", "alltoall"][it % 7]
            # allreduce keeps one size so iteration 7 actually REUSES
            # iteration 0's result buffer as its source
            n = 8 if op == "allreduce" else (8, 256)[it % 2]
            root = it % W
            base = np.arange(n, dtype=np.float32)
            if op == "allreduce":
                if prev is not None and prev.size == n:
                    s = prev  # result reused as source, still resident
                    assert s.is_device_resident
                    expect = W * float(np.asarray(prev.data)[0])
                else:
                    s = a.buffer(data=np.full(n, r + 1.0, np.float32),
                                 device_resident=True)
                    expect = W * (W + 1) / 2
                d = a.buffer((n,), np.float32, device_resident=True)
                a.allreduce(s, d, n)
                np.testing.assert_allclose(
                    d.data, np.full(n, expect, np.float32), rtol=1e-6)
                assert d.is_device_resident
                prev = d
            elif op == "bcast":
                b = (a.buffer(data=base + it, device_resident=True)
                     if r == root else
                     a.buffer((n,), np.float32, device_resident=True))
                a.bcast(b, n, root=root)
                np.testing.assert_allclose(b.data, base + it, rtol=1e-6)
                assert b.is_device_resident
            elif op == "scatter":
                big = a.buffer(data=np.tile(base, W) + r,
                               device_resident=True)
                mine = a.buffer((n,), np.float32, device_resident=True)
                a.scatter(big, mine, n, root=root)
                np.testing.assert_allclose(mine.data, base + root,
                                           rtol=1e-6)
                assert mine.is_device_resident
            elif op == "gather":
                mine = a.buffer(data=base * (r + 1), device_resident=True)
                out = a.buffer((n * W,), np.float32, device_resident=True)
                a.gather(mine, out, n, root=root)
                if r == root:
                    for k in range(W):
                        np.testing.assert_allclose(
                            out.data[k * n:(k + 1) * n], base * (k + 1),
                            rtol=1e-6)
            elif op == "reduce":
                s = a.buffer(data=base + r, device_resident=True)
                d = a.buffer((n,), np.float32, device_resident=True)
                a.reduce(s, d, n, root=root)
                if r == root:
                    np.testing.assert_allclose(
                        d.data, base * W + sum(range(W)), rtol=1e-6)
            elif op == "allgather":
                mine = a.buffer(data=base + 10 * r, device_resident=True)
                out = a.buffer((n * W,), np.float32, device_resident=True)
                a.allgather(mine, out, n)
                for k in range(W):
                    np.testing.assert_allclose(
                        out.data[k * n:(k + 1) * n], base + 10 * k,
                        rtol=1e-6)
            else:  # alltoall
                s = a.buffer(
                    data=np.repeat(np.arange(W, dtype=np.float32), n)
                    + r * 100, device_resident=True)
                d = a.buffer((n * W,), np.float32, device_resident=True)
                a.alltoall(s, d, n)
                for k in range(W):
                    np.testing.assert_allclose(
                        d.data[k * n:(k + 1) * n], r + k * 100, rtol=1e-6)
                assert d.is_device_resident
        return True

    assert all(run_ranks(world, fn, timeout=240.0))
