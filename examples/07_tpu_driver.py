"""The driver API on a device mesh: TpuDevice worlds, algorithm
selectors, split communicators, wire compression.

Run:  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
          python examples/07_tpu_driver.py
(8 virtual devices; on a TPU slice drop the env vars — the same code
compiles to ICI collectives.)

This is the tier a reference user lands on for host-orchestrated
programs: the exact ACCL call surface (buffers, communicators,
allreduce/bcast/..., waitfor chaining) with the dataplane compiled to
XLA collectives over the mesh instead of an FPGA kernel. Pure-JAX
training loops should use accl_tpu.parallel directly (see examples
02/06); this driver tier is for ACCL-style applications.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from accl_tpu.constants import ReduceFunc
from accl_tpu.device.tpu import tpu_world
from accl_tpu.testing import run_ranks


def main():
    accls = tpu_world()  # one rank per mesh device
    W = len(accls)
    print(f"driver world: {W} ranks on "
          f"{accls[0].device.ctx.mesh.devices.ravel()[0].platform}")
    n = 1024

    # fused ring allreduce — the flagship call, XLA psum under the hood
    def allreduce(a):
        src = a.buffer(data=np.full(n, 1.0 + a.rank, np.float32))
        dst = a.buffer((n,), np.float32)
        a.allreduce(src, dst, n)
        dst.sync_from_device()
        return dst.data.copy()

    outs = run_ranks(accls, allreduce)
    expect = sum(1.0 + r for r in range(W))
    assert all((o == expect).all() for o in outs)
    print(f"allreduce: every rank holds the global sum ({expect:.0f})")

    # rooted bcast with an algorithm selector (2-D mesh tree when the
    # mesh allows; the selector surface of the reference's xrt driver)
    def bcast(a):
        buf = (a.buffer(data=np.arange(n, dtype=np.float32))
               if a.rank == 0 else a.buffer((n,), np.float32))
        a.bcast(buf, n, root=0)
        buf.sync_from_device()
        return buf.data.copy()

    for out in run_ranks(accls, bcast):
        assert (out == np.arange(n)).all()
    print("bcast: root payload on every rank")

    # split communicator: the even ranks reduce among themselves while
    # the odd ranks run an independent allgather — concurrently
    evens, odds = list(range(0, W, 2)), list(range(1, W, 2))

    def split_work(a):
        sub = a.split_communicator(evens if a.rank % 2 == 0 else odds)
        if a.rank % 2 == 0:
            src = a.buffer(data=np.full(8, float(a.rank), np.float32))
            dst = a.buffer((8,), np.float32)
            a.allreduce(src, dst, 8, comm=sub)
        else:
            src = a.buffer(data=np.full(4, float(a.rank), np.float32))
            dst = a.buffer((4 * len(odds),), np.float32)
            a.allgather(src, dst, 4, comm=sub)
        dst.sync_from_device()
        return dst.data.copy()

    outs = run_ranks(accls, split_work)
    assert (outs[0] == sum(evens)).all()
    assert (outs[1][:4] == odds[0]).all()
    print("split communicators: disjoint groups progressed concurrently")

    # wire compression by dtype pair: fp32 source, fp16 result
    def compressed(a):
        src = a.buffer(data=np.linspace(0, 1, n).astype(np.float32))
        dst = a.buffer((n,), np.float16)
        a.allreduce(src, dst, n, func=ReduceFunc.SUM)
        dst.sync_from_device()
        return dst.data.copy()

    outs = run_ranks(accls, compressed)
    golden = np.linspace(0, 1, n, dtype=np.float32) * W
    np.testing.assert_allclose(outs[0].astype(np.float32), golden,
                               rtol=2e-3, atol=2e-3)
    print("compressed allreduce: fp16 result within wire precision")

    for a in accls:
        a.deinit()
    print("driver tier on the mesh OK")


if __name__ == "__main__":
    main()
