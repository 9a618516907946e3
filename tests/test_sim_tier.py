"""Socket-daemon tier tests: the same corpus through SimDevice + RankDaemon.

BASELINE config 1 (2-rank send/recv ping-pong through the emulator wire
protocol) lives here.
"""

import numpy as np
import pytest

from accl_tpu import ACCLError, ErrorCode, ReduceFunc
from accl_tpu.testing import run_ranks, sim_world


@pytest.fixture(scope="module")
def world():
    accls = sim_world(4)
    yield accls
    for a in accls:
        a.deinit()


def _data(count, dtype, seed):
    return np.random.default_rng(seed).standard_normal(count).astype(dtype)


def test_pingpong(world):
    """BASELINE config 1: 2-rank fp32 send/recv ping-pong."""
    count = 256

    def fn(a):
        buf = a.buffer((count,), np.float32)
        if a.rank == 0:
            buf.data[:] = _data(count, np.float32, 1)
            a.send(buf, count, dst=1, tag=0)
            a.recv(buf, count, src=1, tag=1)
            return buf.data.copy()
        elif a.rank == 1:
            a.recv(buf, count, src=0, tag=0)
            buf.data[:] *= 2
            a.send(buf, count, dst=0, tag=1)
        return None

    res = run_ranks(world, fn)
    np.testing.assert_allclose(res[0], _data(count, np.float32, 1) * 2,
                               rtol=1e-6)


def test_allreduce(world):
    count = 300
    ins = [_data(count, np.float32, 10 + r) for r in range(4)]

    def fn(a):
        src = a.buffer(data=ins[a.rank])
        dst = a.buffer((count,), np.float32)
        a.allreduce(src, dst, count)
        return dst.data.copy()

    golden = sum(ins)
    for out in run_ranks(world, fn):
        np.testing.assert_allclose(out, golden, rtol=1e-4, atol=1e-5)


def test_bcast_and_gather(world):
    W, count = 4, 32
    golden = _data(count, np.float32, 42)

    def fn(a):
        buf = a.buffer((count,), np.float32)
        if a.rank == 2:
            buf.data[:] = golden
        a.bcast(buf, count, root=2)
        dst = a.buffer((W * count,), np.float32) if a.rank == 0 else None
        a.gather(buf, dst, count, root=0)
        return dst.data.copy() if dst is not None else buf.data.copy()

    res = run_ranks(world, fn)
    for r in range(W):
        np.testing.assert_allclose(
            res[0][r * count:(r + 1) * count], golden, rtol=1e-6)


def test_compressed_send(world):
    count = 64
    golden = _data(count, np.float32, 77)

    def fn(a):
        buf = a.buffer((count,), np.float32)
        if a.rank == 0:
            buf.data[:] = golden
            a.send(buf, count, dst=3, tag=5, compress_dtype=np.float16)
        elif a.rank == 3:
            a.recv(buf, count, src=0, tag=5, compress_dtype=np.float16)
            return buf.data.copy()
        return None

    res = run_ranks(world, fn)
    np.testing.assert_allclose(res[3], golden.astype(np.float16), rtol=1e-3)


def test_async_chain(world):
    a = world[0]
    x = a.buffer(data=np.full(16, 3.0, np.float32))
    y = a.buffer((16,), np.float32)
    z = a.buffer((16,), np.float32)
    h1 = a.copy(x, y, run_async=True)
    h2 = a.combine(16, ReduceFunc.SUM, x, y, z, run_async=True, waitfor=[h1])
    h2.wait()
    z.sync_from_device()
    np.testing.assert_allclose(z.data, np.full(16, 6.0))


def test_timeout_error(world):
    def fn(a):
        if a.rank == 1:
            a.set_timeout(0.3)
            buf = a.buffer((4,), np.float32)
            try:
                with pytest.raises(ACCLError) as ei:
                    a.recv(buf, 4, src=2, tag=9)
                assert ErrorCode.RECEIVE_TIMEOUT_ERROR in ei.value.errors
            finally:
                a.set_timeout(20.0)
        return None

    run_ranks(world, fn)


def test_dump_rx(world):
    assert "RX pool" in world[0].device.dump_rx_buffers()


def test_multiprocess_daemons():
    """True out-of-process tier: daemons in separate python processes,
    driven over the socket protocol (the reference's mpirun-launched
    emulator story, test/host/test_all.py)."""
    import os
    import subprocess
    import sys
    import time

    from accl_tpu.testing import connect_world, free_port_base, run_ranks

    port_base = free_port_base()
    W = 2
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_PLATFORMS"}
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) + os.pathsep + env.get("PYTHONPATH", "")
    procs = [subprocess.Popen(
        [sys.executable, "-m", "accl_tpu.emulator.daemon",
         "--rank", str(r), "--world", str(W), "--port-base", str(port_base)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(W)]
    try:
        time.sleep(1.0)  # daemon startup
        accls = connect_world(port_base, W, timeout=15.0)

        ins = [np.full(64, float(r + 1), np.float32) for r in range(W)]

        def fn(a):
            src = a.buffer(data=ins[a.rank])
            dst = a.buffer((64,), np.float32)
            a.allreduce(src, dst, 64)
            return dst.data.copy()

        for out in run_ranks(accls, fn):
            np.testing.assert_allclose(out, ins[0] + ins[1])
        for a in accls:
            a.deinit()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)


def test_native_daemon():
    """The C++ daemon (native/cclo_emud) is protocol-compatible: the same
    driver + tests run against it unchanged."""
    import os
    import subprocess
    import time

    from accl_tpu.testing import connect_world, free_port_base, run_ranks

    binary = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native", "cclo_emud")
    if not os.path.exists(binary):
        pytest.skip("native daemon not built (make -C native)")

    port_base = free_port_base()
    W = 3
    procs = [subprocess.Popen(
        [binary, "--rank", str(r), "--world", str(W),
         "--port-base", str(port_base)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(W)]
    try:
        time.sleep(0.5)
        accls = connect_world(port_base, W, timeout=15.0)

        # ping-pong with tags
        def pp(a):
            buf = a.buffer((32,), np.float32)
            if a.rank == 0:
                buf.data[:] = 7.5
                a.send(buf, 32, dst=1, tag=3)
            elif a.rank == 1:
                a.recv(buf, 32, src=0, tag=3)
                return buf.data[0]
            return None

        assert run_ranks(accls, pp)[1] == 7.5

        # ring allreduce across all three native daemons
        ins = [np.arange(40, dtype=np.float32) * (r + 1) for r in range(W)]

        def ar(a):
            src = a.buffer(data=ins[a.rank])
            dst = a.buffer((40,), np.float32)
            a.allreduce(src, dst, 40)
            return dst.data.copy()

        for out in run_ranks(accls, ar):
            np.testing.assert_allclose(out, sum(ins), rtol=1e-5)

        # fp16 wire compression through the native compression lanes
        def comp(a):
            src = a.buffer(data=np.full(16, 1.5, np.float32))
            dst = a.buffer((16,), np.float32)
            a.allreduce(src, dst, 16, compress_dtype=np.float16)
            return dst.data[0]

        assert run_ranks(accls, comp)[0] == 4.5

        # reduce/bcast/gather/scatter/alltoall/reduce_scatter quick pass
        def all_colls(a):
            out = {}
            W_, count = W, 6
            src = a.buffer(data=np.full(count, float(a.rank + 1), np.float32))
            dst = a.buffer((count,), np.float32)
            a.reduce(src, dst, count, root=0)
            if a.rank == 0:
                out["reduce"] = dst.data[0]
            buf = a.buffer((count,), np.float32)
            if a.rank == 2:
                buf.data[:] = 9.0
            a.bcast(buf, count, root=2)
            out["bcast"] = buf.data[0]
            big = a.buffer((W_ * count,), np.float32)
            a.gather(src, big if a.rank == 1 else None, count, root=1)
            if a.rank == 1:
                out["gather"] = big.data[::count].tolist()
            rs_src = a.buffer(data=np.tile(
                np.full(count, float(a.rank + 1), np.float32), W_))
            a.reduce_scatter(rs_src, dst, count)
            out["rs"] = dst.data[0]
            return out

        res = run_ranks(accls, all_colls)
        assert res[0]["reduce"] == 6.0
        assert all(r["bcast"] == 9.0 for r in res)
        assert res[1]["gather"] == [1.0, 2.0, 3.0]
        assert all(r["rs"] == 6.0 for r in res)

        # dump through the native daemon
        assert "native" in accls[0].device.dump_rx_buffers()
        for a in accls:
            a.deinit()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)


def test_overlapped_sends_then_recvs(world):
    """Async sends overlap and retire independently of later recvs (eager
    ingress); the polling WAIT keeps the command socket usable while calls
    are outstanding. Note: each device retires calls in FIFO order (the
    reference's single-dispatch-loop semantics), so a recv posted before the
    matching peer's send still works — the send lands eagerly — but a recv
    posted ahead of one's OWN send serializes behind it."""
    def fn(a):
        if a.rank >= 2:
            return None
        peer = 1 - a.rank
        rxb = a.buffer((8,), np.float32)
        txb = a.buffer(data=np.full(8, float(a.rank + 1), np.float32))
        h_tx = a.send(txb, 8, dst=peer, tag=1, run_async=True)
        h_rx = a.recv(rxb, 8, src=peer, tag=1, run_async=True)
        h_tx.wait(20)
        h_rx.wait(20)
        rxb.sync_from_device()
        return rxb.data[0]

    res = run_ranks(world, fn)
    assert res[0] == 2.0 and res[1] == 1.0


def test_status_map_bounded_under_unwaited_chains():
    """The C++ driver's call_chain pattern — wait only the LAST id —
    must not leak a retired-status entry per unwaited link: the daemon
    evicts oldest retired entries past the bound, never an id a blocked
    waiter sleeps on, and a wait for an evicted id reports PENDING."""
    import socket
    import struct

    from accl_tpu.emulator import protocol as P
    from accl_tpu.emulator.daemon import spawn_world

    daemons, pb = spawn_world(1)
    try:
        sock = socket.create_connection(("127.0.0.1", pb), timeout=10)
        rf = sock.makefile("rb")
        NOP = P.pack_call(255, 0, 0, 0, P.DTYPE_CODES["float32"],
                          P.DTYPE_CODES["float32"], 0, 0, 0, 0, 0, 0, 0,
                          [])
        first_id = last_id = None
        for base in range(0, 5000, 250):  # chunked like call_chain
            P.send_frames(sock, [NOP] * 250)
            for _ in range(250):
                reply = P.recv_frame_file(rf)
                assert reply[0] == P.MSG_CALL_ID
                cid = struct.unpack("<I", reply[1:5])[0]
                first_id = cid if first_id is None else first_id
                last_id = cid
        # waiting the last id succeeds; the map stayed bounded
        P.send_frame(sock, bytes([P.MSG_WAIT]) +
                     struct.pack("<Id", last_id, 10.0))
        reply = P.recv_frame_file(rf)
        assert struct.unpack("<I", reply[1:5])[0] == 0
        assert len(daemons[0]._call_status) <= 4100
        # the first id was evicted long ago: a DEFERRED wait still
        # resolves its true outcome (FIFO retirement + the evicted-max
        # watermark infer success; failures survive in the failed-calls
        # map) instead of spuriously timing out
        P.send_frame(sock, bytes([P.MSG_WAIT]) +
                     struct.pack("<Id", first_id, 0.05))
        reply = P.recv_frame_file(rf)
        assert struct.unpack("<I", reply[1:5])[0] == 0
        sock.close()
    finally:
        for d in daemons:
            d.shutdown()


def test_async_recv_pending_past_head_budget(world):
    """An async recv that stays unmatched past the completion worker's
    1 s head budget exercises the PENDING retry rounds, where the
    speculative result readback is withheld for non-retired calls and
    the result must land via the post-retirement read instead."""
    import threading
    import time

    a0, a1 = world[0], world[1]
    payload = _data(64, np.float32, 77)
    rxb = a1.buffer((64,), np.float32)
    h = a1.recv(rxb, 64, src=0, tag=909, run_async=True)
    time.sleep(1.4)  # past the head WAIT budget: at least one retry round
    assert not h.done()
    t = threading.Thread(
        target=lambda: a0.send(a0.buffer(data=payload), 64, dst=1, tag=909))
    t.start()
    h.wait(20)
    t.join()
    np.testing.assert_array_equal(rxb.data, payload)


def test_deep_pipelined_chain_data_dependency(world):
    """An N-deep combine chain whose operands are all the dependency's
    RESULT flows through the wire-waitfor pipeline (batched submission +
    daemon-side FIFO): acc doubles every link."""
    a = world[0]
    depth = 16
    acc = a.buffer(data=np.full(8, 1.0, np.float32))
    h = None
    for _ in range(depth):
        kw = {"waitfor": [h]} if h is not None else {}
        h = a.combine(8, ReduceFunc.SUM, acc, acc, acc, run_async=True,
                      **kw)
    h.wait()
    acc.sync_from_device()
    np.testing.assert_allclose(acc.data, np.full(8, float(2 ** depth)))


def test_chain_operand_hazard_falls_back(world):
    """A chain link whose operand aliases the pending dependency's INPUT
    (not its result) must not push the mirror early: the dependency
    reads its submission-time value, the dependent reads its own. The
    classic reuse pattern: call, mutate the buffer, chained call."""
    a = world[0]
    import time
    x = a.buffer(data=np.full(8, 1.0, np.float32))
    out1 = a.buffer((8,), np.float32)
    out2 = a.buffer((8,), np.float32)
    h1 = a.copy(x, out1, run_async=True)
    # wait for h1's dispatch to have pushed its operand mirror (the
    # async dispatch itself races host mutations — pre-existing
    # submission-time semantics); the hazard under test is ONLY h2's
    # pipelined push overtaking h1's execution
    deadline = time.monotonic() + 5.0
    while getattr(h1, "sim_call_id", None) is None:
        assert time.monotonic() < deadline, "h1 never submitted"
        time.sleep(0.0005)
    x.data[:] = 5.0  # mutated AFTER h1's submission
    h2 = a.copy(x, out2, run_async=True, waitfor=[h1])
    h2.wait(10)
    h1.wait(10)
    out1.sync_from_device()
    out2.sync_from_device()
    np.testing.assert_allclose(out1.data, np.full(8, 1.0))
    np.testing.assert_allclose(out2.data, np.full(8, 5.0))


def test_chain_error_propagates_through_daemon(world):
    """A failed link fails every dependent link daemon-side (the failed-
    call map consulted by the worker), without executing them."""
    a = world[0]
    x = a.buffer(data=np.ones(8, np.float32))
    out = a.buffer((8,), np.float32)
    # an invalid call: recv from an out-of-range rank errors daemon-side
    h1 = a.recv(x, 8, src=3999, run_async=True)
    h2 = a.copy(x, out, run_async=True, waitfor=[h1])
    h3 = a.copy(out, x, run_async=True, waitfor=[h2])
    with pytest.raises(ACCLError):
        h3.wait(10)
    assert h3.error_word != 0
