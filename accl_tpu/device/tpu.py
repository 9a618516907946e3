"""TPU backend: the ACCL call surface executed on a jax device mesh.

Architecture (the survey's "hard part (a)" — two-sided semantics on an SPMD
substrate): one process is the SPMD controller of all ranks (standard JAX).
Each rank still gets its own ``TpuDevice`` view + ``ACCL`` driver instance,
so the same rank-parallel test corpus drives every tier. Cross-rank
coordination happens in a host-side rendezvous:

* **Collectives** rendezvous all member ranks' calls (matched in per-rank
  program order, MPI semantics); the last arriving rank executes ONE
  shard_map program over the mesh (MeshCollectives) and scatters results
  into every rank's buffer.
* **send** is eager: the payload is snapshotted onto the sender's device
  and the call completes (reference parity: eager ingress lets send
  finish before recv posts). **recv** matches pending sends by
  ``(comm, src, dst, tag)`` + sequence order — that host rendezvous is
  control plane only; the DATA then crosses the device fabric via one
  ppermute program (``TpuContext.exchange_transfer``), riding ICI on a
  real mesh exactly like the reference's send/recv ride its transport
  (ccl_offload_control.c:339-380).

Buffer staging has two modes:

* **Host-mirror buffers** (the default) stage through host numpy per
  call — API parity with the emulator corpus, ~5x per-call overhead
  (``benchmarks/driver_overhead.py``).
* **Device-resident buffers** (``ACCL.buffer(data=<jax.Array>)`` or
  ``device_resident=True`` — the reference's ``to_from_fpga=False``)
  skip host staging entirely: dense collectives assemble the per-rank
  arrays into the flat global with
  ``jax.make_array_from_single_device_arrays``, run one cached program,
  and rebind each rank's dst to its result shard; send snapshots are
  zero-copy (jax.Arrays are immutable). This closes most of the tier
  gap; ``MeshCollectives`` inside your own pjit/shard_map program
  remains the absolute-peak path bench.py measures.
"""

from __future__ import annotations

import collections
import math
import queue
import threading
import time
from typing import Sequence

import numpy as np

import jax
import jax.numpy as jnp

from ..buffer import ACCLBuffer
from ..call import CallDescriptor, CallHandle
from ..communicator import Communicator
from ..constants import (ACCLError, CCLOp, CollectiveAlgorithm, Compression,
                         StreamFlags,
                         DEFAULT_MAX_SEGMENT_SIZE, DEFAULT_TIMEOUT_S,
                         ErrorCode, ReduceFunc, check_algorithm)
from ..emulator.executor import DeviceMemory
from ..log import get_logger
from ..parallel.collectives import MeshCollectives, _wire_name
from ..parallel.mesh import make_mesh
from ..parallel.tree import Tree2DCollectives
from ..rma.window import WindowRegistry
from ..tracing import SPANS, annotate, watch_lowerings
from .base import Device

log = get_logger(__name__)


def _noncanonical(dtype) -> bool:
    """True for dtypes jax cannot represent with x64 off (int64/f64 →
    canonicalized to 32 bits). Payloads of these dtypes must NEVER touch
    jax.device_put or a jnp cast — both silently truncate — so every
    datapath gates on this ONE predicate: stream-port staging, the
    streamed-local ops, and the cross-rank send refusal."""
    d = np.dtype(dtype)
    return jax.dtypes.canonicalize_dtype(d) != d


def _payload_bytes(desc: CallDescriptor, count: int | None = None) -> int:
    """Uncompressed bytes of ``count`` (default: the call's) elements, for
    a span's ``nbytes``."""
    cfg = desc.arithcfg
    n = desc.count if count is None else count
    return n * cfg.uncompressed_elem_bytes if cfg is not None else 0


def _factor_2d(w: int) -> tuple[int, int]:
    """Largest divisor pair (outer, inner) with outer <= inner — the 2D
    mesh shape the tree collectives ride. (1, w) means no 2D structure."""
    o = int(w ** 0.5)
    while o > 1 and w % o:
        o -= 1
    return o, w // o

_COLLECTIVES = {CCLOp.bcast, CCLOp.scatter, CCLOp.gather, CCLOp.reduce,
                CCLOp.allgather, CCLOp.allreduce, CCLOp.reduce_scatter,
                CCLOp.alltoall, CCLOp.barrier}

# on-device combine arithmetic for the streamed/fused local datapath
_COMBINE_JNP = {ReduceFunc.SUM: jnp.add, ReduceFunc.MAX: jnp.maximum,
                ReduceFunc.MIN: jnp.minimum, ReduceFunc.PROD: jnp.multiply}


def _window_land(dst, payload, off):
    flat = jax.lax.dynamic_update_slice(dst.reshape(-1), payload, (off,))
    return flat.reshape(dst.shape)


# RMA put landing: one donated program updates the window buffer in place
# (XLA reuses the donated allocation), so a put into a device-resident
# window never materializes a second full-size copy, let alone a host
# round-trip. `off` is a traced element offset — one compile per window
# geometry, not per offset.
_window_put_prog = jax.jit(_window_land, donate_argnums=(0,))


class _XchgEntry:
    """One matched p2p transfer waiting in the exchange window."""

    __slots__ = ("src", "dst", "payload", "result", "error", "done")

    def __init__(self, src: int, dst: int, payload):
        self.src = src
        self.dst = dst
        self.payload = payload
        self.result = None
        self.error: BaseException | None = None
        self.done = threading.Event()


class TpuContext:
    """Shared state of an N-rank TPU-backed world (single SPMD controller)."""

    def __init__(self, world_size: int | None = None, mesh=None,
                 axis_name: str = "rank", platform: str | None = None,
                 algorithm: str = "xla"):
        watch_lowerings()
        if mesh is None:
            mesh = make_mesh((world_size,) if world_size else None,
                             (axis_name,), platform=platform)
        self.mesh = mesh
        self.axis_name = axis_name
        self.world_size = mesh.shape[axis_name]
        self.coll = MeshCollectives(mesh, axis_name)
        self._subcolls: dict[int, MeshCollectives] = {}
        self._subtrees: dict[int, Tree2DCollectives | None] = {}
        self.tree = self._make_tree(
            list(np.asarray(mesh.devices).reshape(-1)))
        self.algorithm = algorithm
        self.devices: list[TpuDevice | None] = [None] * self.world_size
        # rendezvous state
        self._lock = threading.Condition()
        # (comm_id, op_index) -> {comm-local rank: (desc, handle, deadline)}
        self._pending: dict[tuple, dict] = {}
        self._sweeper: threading.Thread | None = None
        # (comm_id, src_g, dst_g) -> deque of (tag, payload jax.Array).
        # Payloads now live in device memory (eager-send snapshots), so
        # unmatched sends pin scarce HBM: like the emulator's finite
        # spare-buffer pool, the parked-send count is bounded and an
        # overflowing send fails with the pool-overflow error instead of
        # leaking (emulator/executor.py RxBufferPool parity).
        self._sends: dict[tuple, collections.deque] = \
            collections.defaultdict(collections.deque)
        self.max_parked_sends = 1024  # across the context, like nbufs
        self._parked_sends = 0        # running count (guarded by _lock)
        # filler shards for the exchange program: ranks that are neither
        # src nor dst of a transfer still contribute an operand shard.
        # Cached per (device, size, dtype) — they're constant zeros.
        self._zeros: dict[tuple, jax.Array] = {}
        self._zeros_mu = threading.Lock()
        # exchange window: comm_id -> queued _XchgEntry; comm_ids with a
        # live batch executor (guarded by _lock)
        self._xchg_pending: dict[int, list] = collections.defaultdict(list)
        self._xchg_running: set[int] = set()

    # cap on cached filler shards: a size sweep would otherwise pin one
    # device array per distinct (device, size, dtype) forever
    _MAX_ZERO_CACHE = 64

    def zero_shard(self, dev, n: int, dtype) -> jax.Array:
        key = (dev, n, np.dtype(dtype).name)
        # fast path without the lock: dict reads are atomic, and a stale
        # miss only costs a redundant zeros build below
        arr = self._zeros.get(key)
        if arr is None:
            arr = jax.device_put(np.zeros(n, dtype), dev)
            with self._zeros_mu:  # eviction+insert race-free (concurrent
                if len(self._zeros) >= self._MAX_ZERO_CACHE:  # recv threads)
                    # FIFO eviction (dict preserves insertion order): drop
                    # the oldest size class rather than growing device
                    # memory
                    self._zeros.pop(next(iter(self._zeros)), None)
                arr = self._zeros.setdefault(key, arr)
        return arr

    def assemble_flat(self, coll: MeshCollectives,
                      shards: list) -> jax.Array:
        """Build the flat global (W*n,) array from per-rank 1-D device
        arrays without host staging: each shard must already live on (or
        is moved to) its comm-local rank's device."""
        devs = coll.device_list
        n = shards[0].shape[0]
        placed = []
        for dev, arr in zip(devs, shards):
            # arr.device is a cheap C property on single-device arrays;
            # devices() builds a frozenset per call (~10us each)
            if getattr(arr, "device", None) != dev:
                arr = jax.device_put(arr, dev)
            placed.append(arr)
        return jax.make_array_from_single_device_arrays(
            (len(devs) * n,), coll.flat_sharding, placed)

    def exchange_transfer(self, comm: Communicator, payload: jax.Array,
                          src_local: int, dst_local: int) -> jax.Array:
        """Move one matched send/recv payload across the device fabric:
        a ppermute program over the communicator's mesh (parity: the
        reference's send/recv ride the real transport end-to-end,
        ccl_offload_control.c:339-380). Returns the received shard (on
        the destination rank's device).

        Matched pairs BATCH opportunistically: transfers deposited while
        an exchange program is running ride the next program together
        (one ppermute with per-pair payloads) instead of one full-mesh
        program each — K concurrent sendrecvs execute in <=2 programs,
        not K, with no added latency for a solo transfer (the first
        arrival never waits for a window to fill)."""
        entry = _XchgEntry(src_local, dst_local, payload)
        cid = comm.comm_id
        with self._lock:
            self._xchg_pending[cid].append(entry)
        # Cooperative leadership, ONE batch per claim: any thread whose
        # entry is pending may claim the free executor flag, run exactly
        # the window present at claim time, then hand off — so a leader
        # is never captured by other ranks' sustained traffic (bounded
        # extra work: one batch), while transfers deposited during a
        # running program still pile into the next claim together.
        while True:
            with self._lock:
                if entry.done.is_set():
                    break
                claimed = (cid not in self._xchg_running
                           and bool(self._xchg_pending[cid]))
                if claimed:
                    self._xchg_running.add(cid)
                    batch = self._xchg_pending[cid]
                    self._xchg_pending[cid] = []
            if not claimed:
                # Wait on the shared Condition: the leader notifies it
                # after every completed round AND on batch handoff, so a
                # waiter wakes immediately both when its own transfer
                # completes mid-batch and when leadership frees up —
                # sleeping on the per-entry Event instead would miss the
                # handoff notify and eat a full poll tick. The short
                # timeout stays as the backstop if a leader died.
                with self._lock:
                    if not entry.done.is_set() and cid in self._xchg_running:
                        self._lock.wait(0.05)
                continue
            try:
                self._run_exchange_batch(comm, batch)
            except BaseException as exc:
                for e in batch:
                    if not e.done.is_set():  # completed rounds stand
                        e.error = exc
                        e.done.set()
            finally:
                with self._lock:
                    self._xchg_running.discard(cid)
                    self._lock.notify_all()
        if entry.error is not None:
            raise entry.error
        return entry.result

    def _run_exchange_batch(self, comm: Communicator, entries: list):
        """Execute one window of matched transfers: entries group by
        payload geometry, each group splits greedily into permutation
        rounds (a ppermute source/destination appears once per round),
        and every round is ONE exchange program."""
        coll = self.coll_for(comm)
        devs = coll.device_list
        groups: dict[tuple, list] = collections.defaultdict(list)
        for e in entries:
            groups[(e.payload.shape[0], str(e.payload.dtype))].append(e)
        for (n, _dt), group in groups.items():
            remaining = group
            while remaining:
                round_entries, nxt = [], []
                srcs, dsts = set(), set()
                for e in remaining:
                    if e.src in srcs or e.dst in dsts:
                        nxt.append(e)   # conflicts ride the next round
                    else:
                        srcs.add(e.src)
                        dsts.add(e.dst)
                        round_entries.append(e)
                remaining = nxt
                by_src = {e.src: e for e in round_entries}
                shards = [by_src[r].payload if r in by_src
                          else self.zero_shard(
                              d, n, round_entries[0].payload.dtype)
                          for r, d in enumerate(devs)]
                x = self.assemble_flat(coll, shards)
                pairs = tuple(sorted((e.src, e.dst)
                                     for e in round_entries))
                out = coll.exchange_flat(x, pairs)
                by_dst = {e.dst: e for e in round_entries}
                for s in out.addressable_shards:
                    r = (s.index[0].start or 0) // n
                    e = by_dst.get(r)
                    if e is not None:
                        e.result = s.data
                        e.done.set()
                for e in round_entries:   # paranoia: no silent waiter
                    if not e.done.is_set():
                        e.error = RuntimeError(
                            "destination shard missing from exchange")
                        e.done.set()
                with self._lock:
                    # wake Condition sleepers whose entries just
                    # completed (they no longer sleep on the Event)
                    self._lock.notify_all()

    def device(self, rank: int) -> "TpuDevice":
        if self.devices[rank] is None:
            self.devices[rank] = TpuDevice(self, rank)
        return self.devices[rank]

    # -- deadline sweeper ---------------------------------------------------
    def _ensure_sweeper(self):
        """Start the (single, lazy) deadline sweeper. Caller holds _lock.

        Members of an incomplete rendezvous group no longer park a thread
        each, so their per-call timeout is enforced centrally: the sweeper
        fails any deposit whose deadline passed with
        RECEIVE_TIMEOUT_ERROR and removes its slot — a group missing a
        member can then never complete, and its remaining deposits expire
        on their own deadlines (the old per-waiter semantics)."""
        if self._sweeper is None:
            self._sweeper = threading.Thread(target=self._sweep_loop,
                                             daemon=True,
                                             name="tpu-coll-sweeper")
            self._sweeper.start()

    def _sweep_loop(self):
        from ..constants import ACCLError
        idle_scans = 0
        while True:
            with self._lock:
                now = time.monotonic()
                expired = []
                next_dl = None
                for key, group in list(self._pending.items()):
                    for r, (d, h, dl) in list(group.items()):
                        if dl <= now:
                            group.pop(r)
                            expired.append(h)
                        elif next_dl is None or dl < next_dl:
                            next_dl = dl
                    if not group:
                        self._pending.pop(key, None)
                if not self._pending and not expired:
                    idle_scans += 1
                    if idle_scans >= 10:
                        # nothing pending for ~2s: retire rather than
                        # polling forever (long-lived processes creating
                        # many worlds would accumulate pollers); the next
                        # incomplete deposit restarts it
                        self._sweeper = None
                        return
                else:
                    idle_scans = 0
            for h in expired:
                if h is not None:
                    err = int(ErrorCode.RECEIVE_TIMEOUT_ERROR)
                    h.complete(err, exception=ACCLError(
                        err, "collective group incomplete at deadline"))
            # Deposits never wake the sweeper (a wakeup per member per
            # collective is pure GIL churn on the hot path, and waiting
            # on ctx._lock would make every send's notify_all a spurious
            # wake). It polls: 200 ms cadence when idle, the exact
            # earliest deadline when groups are pending — a timeout may
            # fire up to one poll late, which RECEIVE_TIMEOUT semantics
            # tolerate.
            now = time.monotonic()
            time.sleep(0.2 if next_dl is None
                       else min(max(next_dl - now, 0.001), 0.2))

    @staticmethod
    def _make_tree(devs) -> Tree2DCollectives | None:
        """Hierarchical collectives over the same devices folded into the
        largest 2D factorization — the bandwidth-correct path for rooted
        ops at scale (BASELINE config 4's 32-rank (8,4) trees). None when
        the world has no 2D structure (prime or < 4 ranks)."""
        from jax.sharding import Mesh
        o, i = _factor_2d(len(devs))
        if o < 2:
            return None
        return Tree2DCollectives(
            Mesh(np.asarray(devs).reshape(o, i), ("outer", "inner")))

    def _comm_devices(self, comm: Communicator) -> list:
        """The communicator's devices in comm-local rank order (one
        rank->device convention for every sub-mesh built from the world)."""
        world_devs = list(np.asarray(self.mesh.devices).reshape(-1))
        return [world_devs[r.global_rank] for r in comm.ranks]

    def coll_for(self, comm: Communicator) -> MeshCollectives:
        """Collectives bound to the communicator's sub-mesh: member global
        ranks select their devices from the world mesh (a split comm runs
        over its own axis, so axis_index == comm-local rank). Cache fills
        take the ctx lock — launchers of disjoint comms run concurrently."""
        if comm.size == self.world_size:
            return self.coll
        key = comm.comm_id
        with self._lock:
            cached = self._subcolls.get(key)
        if cached is not None:
            return cached
        from jax.sharding import Mesh
        sub = MeshCollectives(
            Mesh(np.asarray(self._comm_devices(comm)), (self.axis_name,)),
            self.axis_name)
        with self._lock:
            return self._subcolls.setdefault(key, sub)

    def tree_for(self, comm: Communicator) -> Tree2DCollectives | None:
        """The communicator's 2D tree context (None when its size has no
        2D factorization)."""
        if comm.size == self.world_size:
            return self.tree
        key = comm.comm_id
        with self._lock:
            if key in self._subtrees:
                return self._subtrees[key]
        tree = self._make_tree(self._comm_devices(comm))
        with self._lock:
            return self._subtrees.setdefault(key, tree)


class DeviceStreamPort:
    """Device-resident external-kernel stream ports for one rank.

    The TPU-native mapping of the reference's AXIS stream ports
    (SWITCH_M_BYPASS, streamdefines.h:39): entries are 1-D jax arrays
    living on this rank's device — a staging ring the fused ops read
    from and write to WITHOUT the payload ever visiting the host.
    Continuous-stream semantics mirror the emulator executor's ports:
    a take may span entries and consume one partially; a shortfall
    blocks to a deadline and consumes nothing on timeout (stalled-AXIS
    parity, KRNL_TIMEOUT upstream)."""

    def __init__(self, device):
        self.dev = device                     # the rank's jax device
        self._in: collections.deque = collections.deque()
        self._in_off = 0                      # consumed prefix of _in[0]
        self._out: collections.deque = collections.deque()
        self._out_off = 0
        self._cv = threading.Condition()

    def push(self, data) -> None:
        # own the bytes: device_put ALIASES host memory on some backends
        # (cpu), and the host-preserved branch would otherwise keep a
        # view — either way a caller mutating its array after push would
        # corrupt the staged entry (same eager-snapshot contract as
        # _do_send)
        host = np.array(data, copy=True).reshape(-1)
        if not _noncanonical(host.dtype):
            entry = jax.device_put(host, self.dev)  # one transfer
        else:
            # dtype jax cannot represent with x64 off (int64/f64): keep
            # the host array — truncating user bits on a stream port is
            # never acceptable (the emulator tiers preserve them)
            entry = host
        with self._cv:
            self._in.append(entry)
            self._cv.notify_all()

    @staticmethod
    def _avail(q, off) -> int:
        return sum(e.shape[0] for e in q) - off

    @staticmethod
    def _assemble(q, off, count, dtype):
        """Pop ``count`` elements off the front of ``q`` (device slices,
        concatenated on device; host-preserved 64-bit entries assemble
        on host so their bits survive). Returns (array, new_off)."""
        pieces = []
        need = count
        while need:
            e = q[0]
            take = min(need, e.shape[0] - off)
            piece = e if (off == 0 and take == e.shape[0]) \
                else e[off:off + take]
            pieces.append(piece)
            need -= take
            off += take
            if off == e.shape[0]:
                q.popleft()
                off = 0
        if any(isinstance(p, np.ndarray) for p in pieces):
            out = (pieces[0] if len(pieces) == 1
                   else np.concatenate([np.asarray(p) for p in pieces]))
            if dtype is not None and out.dtype != np.dtype(dtype):
                out = out.astype(dtype)
            return out, off
        out = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)
        if dtype is not None and out.dtype != jnp.dtype(dtype):
            out = out.astype(dtype)
        return out, off

    def take(self, count: int, dtype, deadline: float):
        """Blocking stream-in read of exactly ``count`` elements; None on
        timeout (nothing consumed — a retry after the rest arrives must
        succeed)."""
        with self._cv:
            while self._avail(self._in, self._in_off) < count:
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._cv.wait(remaining):
                    return None
            out, self._in_off = self._assemble(self._in, self._in_off,
                                               count, dtype)
            return out

    def put_out(self, arr) -> None:
        with self._cv:
            self._out.append(arr.reshape(-1))
            self._cv.notify_all()

    def put_in(self, arr) -> None:
        """Remote-stream delivery (a peer's stream_put lands here)."""
        with self._cv:
            self._in.append(arr.reshape(-1))
            self._cv.notify_all()

    def pop(self, timeout: float = 0.0, count: int | None = None):
        """Stream-out read: ``count`` elements across entries, or the
        next entry whole (count None/0). IndexError when it never fills
        (emulator pop_stream_out parity)."""
        deadline = time.monotonic() + timeout
        if not count:
            count = None
        with self._cv:
            while True:
                if count is None:
                    if self._out:
                        e = self._out[0]
                        if self._out_off:
                            e, _ = self._assemble(
                                self._out, self._out_off,
                                e.shape[0] - self._out_off, None)
                            self._out_off = 0
                        else:
                            self._out.popleft()
                        return e
                elif self._avail(self._out, self._out_off) >= count:
                    out, self._out_off = self._assemble(
                        self._out, self._out_off, count, None)
                    return out
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self._cv.wait(remaining):
                    raise IndexError("stream-out port empty")

    def reset(self) -> None:
        with self._cv:
            self._in.clear()
            self._out.clear()
            self._in_off = self._out_off = 0


class TpuDevice(Device):
    """One rank's view of the TPU-backed world."""

    def __init__(self, ctx: TpuContext, rank: int):
        self.ctx = ctx
        self.rank = rank
        self.mem = DeviceMemory()          # host mirrors of device buffers
        # device-resident buffers (no host mirror): address -> ACCLBuffer
        # whose .jax is the live array on this rank's device
        self.dev_bufs: dict[int, ACCLBuffer] = {}
        self.my_device = list(
            np.asarray(ctx.mesh.devices).reshape(-1))[rank]
        self.windows = WindowRegistry()    # one-sided RMA address space
        self.comms: dict[int, Communicator] = {}
        self.comm: Communicator | None = None
        self.timeout = DEFAULT_TIMEOUT_S
        self.max_segment_size = DEFAULT_MAX_SEGMENT_SIZE
        self.profiling = False  # armed by the start_profiling config call
        self.sport = DeviceStreamPort(self.my_device)
        self._coll_index: dict[int, int] = collections.defaultdict(int)
        self._calls: queue.Queue = queue.Queue()
        self._worker = threading.Thread(target=self._run, daemon=True,
                                        name=f"tpu-rank{rank}")
        self._worker.start()

    # -- Device interface --------------------------------------------------
    def register_buffer(self, buf: ACCLBuffer):
        if buf.is_device_resident:
            self.dev_bufs[buf.address] = buf
        else:
            self.mem.register(buf.address, buf.data)

    def deregister_buffer(self, buf: ACCLBuffer):
        if buf.is_device_resident:
            self.dev_bufs.pop(buf.address, None)
        else:
            self.mem.deregister(buf.address)

    # -- one-sided RMA windows (accl_tpu/rma) ------------------------------
    def register_window(self, wid: int, addr: int, nbytes: int):
        self.windows.register(wid, addr, nbytes)

    def deregister_window(self, wid: int):
        self.windows.deregister(wid)

    # -- device-resident storage (the to_from_fpga=False fast path) --------
    def adopt_device_array(self, arr):
        """Home a live jax.Array on this rank's mesh device. Committed
        single-device arrays already there are adopted zero-copy."""
        devs = arr.devices()
        if len(devs) != 1:
            raise ValueError(
                "device-resident ACCL buffers wrap single-device arrays "
                "(one rank, one device); got a sharded array — pass it "
                "to MeshCollectives / your shard_map program directly")
        if list(devs)[0] != self.my_device:
            arr = jax.device_put(arr, self.my_device)
        return arr

    def make_device_array(self, shape, dtype, init=None):
        if _noncanonical(dtype):
            # with x64 off, device_put would quietly canonicalize the
            # array to 32 bits AT CREATION — every later read of the
            # "int64/f64 device buffer" would see truncated values.
            # Refuse here, the root of that datapath, rather than let
            # _write_result discover the corruption later.
            raise ValueError(
                f"device-resident buffers cannot hold {np.dtype(dtype).name}"
                f" (jax x64-off canonicalizes it to 32 bits); use a "
                f"host-mirror buffer for 64-bit dtypes")
        host = (np.zeros(shape, dtype) if init is None
                else np.asarray(init, dtype).reshape(shape))
        return jax.device_put(host, self.my_device)

    def configure_communicator(self, comm: Communicator,
                               tenant: str | None = None):
        # tenant grouping accepted for interface parity; the TPU tier's
        # per-tenant scheduling lives in the service layer upstream
        self.comms[comm.comm_id] = comm
        if self.comm is None:
            self.comm = comm

    def set_timeout(self, timeout: float):
        self.timeout = timeout

    def set_max_segment_size(self, nbytes: int):
        self.max_segment_size = nbytes

    def topology(self):
        """Mesh tier: an ICI hop is ~a microsecond and per-link bandwidth
        is in the 100 GB/s class on real chips; on the CPU-mesh stand-in
        the same ordering holds (host collectives, negligible per-hop
        software cost vs the emulator tiers)."""
        from ..tuner.cost import Topology
        return Topology(world_size=self.ctx.world_size, alpha_us=1.0,
                        beta_gbps=100.0, tier="tpu")

    def auto_resolvable_ops(self):
        """The rooted ops (bcast/scatter/gather/reduce) keep their AUTO:
        on 2D meshes it lowers to the hierarchical tree (O(outer+inner)
        fan-out), and a tuner resolving AUTO to ROUND_ROBIN/RING would
        force the masked 1-D lowering — allreduce/allgather-class
        traffic regardless of root — based on cost models shaped for the
        move-engine tiers. (bcast does have a TREE selector, but the
        tuner's small-message choice would be ROUND_ROBIN, the exact
        degradation; callers who want the 1-D path can force it.) The
        dense collectives map cleanly onto the xla/ring axis the tuner
        chooses between."""
        return frozenset({"allreduce", "allgather", "reduce_scatter"})

    # Inline eligibility in the submitting thread, preserving the async
    # contract (call_async must not block an async caller on real work):
    # - nop/config are trivial — always inline.
    # - collectives always inline their DEPOSIT (non-blocking, ~10us);
    #   when the deposit completes the group, the heavy launch runs
    #   inline only for synchronous callers (inline_ok — they'd block in
    #   wait() anyway) and hops to the worker for async ones.
    # - send/recv/copy/combine do real work (staging, or blocking on a
    #   peer for recv) — inline only when the caller declared it will
    #   immediately wait (inline_ok).
    _TRIVIAL_OPS = {CCLOp.nop, CCLOp.config}
    _SYNC_INLINE_OPS = {CCLOp.send, CCLOp.recv, CCLOp.copy, CCLOp.combine}

    def call_async(self, desc: CallDescriptor,
                   waitfor: Sequence[CallHandle] = (), *,
                   inline_ok: bool = False) -> CallHandle:
        handle = CallHandle(context=desc.scenario.name)
        op = desc.scenario
        # Inline fast path: skip the worker-thread hop (queue + wakeup +
        # GIL handoff per call — the dominant per-call cost of this tier)
        # whenever per-rank FIFO order is provable: nothing queued or
        # running on the worker (the shared inline gate) and every
        # dependency already retired.
        if (op in self._TRIVIAL_OPS or op in _COLLECTIVES
                or (op in self._SYNC_INLINE_OPS and inline_ok)) \
                and self._inline_begin(waitfor):
            try:
                self._run_one(desc, waitfor, handle,
                              defer_launch=(op in _COLLECTIVES
                                            and not inline_ok))
            finally:
                self._inflight_done()
            return handle
        self._inflight_add()
        self._calls.put((desc, tuple(waitfor), handle))
        return handle

    def soft_reset(self):
        with self.ctx._lock:
            self.ctx._sends.clear()
            self.ctx._parked_sends = 0
        self._coll_index.clear()
        # stale cross-epoch stream data must not leak to the next
        # consumer (emulator reset_streams parity)
        self.sport.reset()

    def deinit(self):
        self._calls.put(None)

    # -- worker ------------------------------------------------------------
    def _run(self):
        while True:
            item = self._calls.get()
            if item is None:
                return
            try:
                if callable(item):
                    item()  # deferred group launch (async last arrival)
                else:
                    desc, waitfor, handle = item
                    self._run_one(desc, waitfor, handle)
            finally:
                self._inflight_done()

    def _run_one(self, desc: CallDescriptor, waitfor, handle: CallHandle,
                 defer_launch: bool = False):
        """Retire one call in the current thread. Completes ``handle``
        unless the call parked in a rendezvous group (collective deposit:
        the group-completing rank — or the deadline sweeper — completes
        it). ``defer_launch`` hops a group-completing launch to the
        worker thread instead of running it here (async submissions must
        not block in call_async)."""
        from ..constants import ACCLError
        try:
            if (desc.deadline is not None
                    and time.monotonic() >= desc.deadline):
                # queued past the caller's bound: the caller's wait already
                # raised, so executing now would mutate buffers it has
                # moved on from — fail instead of running late
                handle.complete(int(ErrorCode.RECEIVE_TIMEOUT_ERROR))
                return
            for dep in waitfor:
                dep.wait(self.timeout if desc.deadline is None
                         else max(0.0, desc.deadline - time.monotonic()))
            err = self._execute(desc, handle, defer_launch)
            if err is not None:
                handle.complete(err)
        except ACCLError as exc:
            handle.complete(exc.error_word, exception=exc)
        except TimeoutError as exc:
            handle.complete(int(ErrorCode.RECEIVE_TIMEOUT_ERROR),
                            exception=exc)
        except Exception as exc:  # noqa: BLE001
            handle.complete(int(ErrorCode.INVALID_CALL), exception=exc)

    # -- operand staging ---------------------------------------------------
    # Host staging is spanned while SPANS is armed: accl.stage.read (the
    # D2H read or host-mirror read), accl.stage.reduce (the numpy combine)
    # and accl.stage.write (the landing, an H2D device_put for a
    # device-resident buffer).
    def _read_operand(self, addr: int, count: int, desc, which: Compression
                      ) -> np.ndarray:
        if SPANS.enabled:
            with annotate("accl.stage.read", call=desc.span_call,
                          nbytes=_payload_bytes(desc, count)):
                return self._read_staged(addr, count, desc, which)
        return self._read_staged(addr, count, desc, which)

    def _read_staged(self, addr: int, count: int, desc, which: Compression
                     ) -> np.ndarray:
        cfg = desc.arithcfg
        buf = self.dev_bufs.get(addr)
        if buf is not None:
            # device-resident source on a host-staged path: one D2H read.
            # The stored dtype IS the array's dtype (no separate
            # compressed mirror exists for device buffers).
            arr = np.asarray(buf.jax).reshape(-1)
            if count > arr.size:
                from ..constants import ACCLError
                raise ACCLError(int(ErrorCode.DMA_SIZE_ERROR),
                                f"read past device buffer end "
                                f"({count} > {arr.size})")
            return arr[:count].astype(cfg.uncompressed_dtype, copy=False)
        stored = (cfg.compressed_dtype if desc.compression & which
                  else cfg.uncompressed_dtype)
        return self.mem.read(addr, count, stored).astype(
            cfg.uncompressed_dtype, copy=False)

    def _write_result(self, addr: int, data: np.ndarray, desc):
        if SPANS.enabled:
            with annotate("accl.stage.write", call=desc.span_call,
                          nbytes=np.asarray(data).nbytes):
                return self._write_staged(addr, data, desc)
        return self._write_staged(addr, data, desc)

    def _write_staged(self, addr: int, data: np.ndarray, desc):
        cfg = desc.arithcfg
        out = (cfg.compressed_dtype
               if desc.compression & Compression.RES_COMPRESSED
               else cfg.uncompressed_dtype)
        buf = self.dev_bufs.get(addr)
        if buf is not None:
            if _noncanonical(np.dtype(out)):
                # a device-resident landing re-enters _rebind_dev, whose
                # device_put canonicalizes int64/f64 to 32 bits — the
                # silent-truncation path every other noncanon gate in
                # this file exists to prevent. make_device_array rejects
                # creating such buffers, so this guards adopted/aliased
                # corners: refuse loudly rather than corrupt the result.
                from ..constants import ACCLError
                raise ACCLError(
                    int(ErrorCode.INVALID_CALL),
                    f"{np.dtype(out).name} result cannot land in a "
                    f"device-resident buffer (jax x64-off would truncate "
                    f"it); use a host-mirror buffer for 64-bit dtypes")
            self._rebind_dev(buf, np.asarray(data, dtype=out))
            return
        self.mem.write(addr, np.asarray(data, dtype=out))

    def _rebind_dev(self, buf: ACCLBuffer, data):
        """Land a result in a device-resident buffer. jax.Arrays are
        immutable, so a full-size result replaces the array; a partial
        result (segmented host paths) does read-modify-write."""
        n = math.prod(np.shape(data))
        if n == buf.size:
            arr = data if isinstance(data, jax.Array) else \
                jax.device_put(np.asarray(data), self.my_device)
            if arr.dtype != buf.dtype:
                arr = arr.astype(buf.dtype)
            if arr.shape != buf.shape:
                arr = arr.reshape(buf.shape)
            buf._rebind(arr)
            return
        host = np.asarray(buf.jax).reshape(-1).copy()
        host[:n] = np.asarray(data, dtype=buf.dtype).reshape(-1)
        buf._rebind(jax.device_put(host.reshape(buf.shape),
                                   self.my_device))

    # -- execution ---------------------------------------------------------
    def _execute(self, desc: CallDescriptor, handle: CallHandle,
                 defer_launch: bool = False) -> int | None:
        """Returns the call's error word, or None when the call parked in
        a rendezvous group and ``handle`` will be completed elsewhere."""
        op = desc.scenario
        if op == CCLOp.nop:
            return 0
        if op == CCLOp.config:
            return self.apply_config(desc)  # shared dispatch (Device base)
        if desc.stream_flags and op not in (CCLOp.copy, CCLOp.combine,
                                            CCLOp.send, CCLOp.recv):
            # streamed operands on the p2p/local ops ride the device-
            # resident ports (DeviceStreamPort); for collectives a
            # streamed operand belongs INSIDE the jitted program — reject
            # explicitly rather than silently executing a memory-only
            # variant (the emulator tiers silently ignore the flags
            # there, which is the one behavior we refuse to copy)
            return int(ErrorCode.STREAM_NOT_SUPPORTED)
        comm = self.comms.get(desc.comm_id)
        if comm is None:
            return int(ErrorCode.COMM_NOT_CONFIGURED)
        s_op0 = bool(desc.stream_flags & StreamFlags.OP0_STREAM)
        s_res = bool(desc.stream_flags & StreamFlags.RES_STREAM)
        if op == CCLOp.copy:
            if s_op0 or s_res:
                return self._streamed_local(desc, s_op0, s_res, None)
            data = self._read_operand(desc.addr_0, desc.count, desc,
                                      Compression.OP0_COMPRESSED)
            self._write_result(desc.addr_2, data, desc)
            return 0
        if op == CCLOp.combine:
            if s_op0 or s_res:
                return self._streamed_local(desc, s_op0, s_res,
                                            desc.function)
            from ..emulator.executor import _REDUCERS
            a = self._read_operand(desc.addr_0, desc.count, desc,
                                   Compression.OP0_COMPRESSED)
            b = self._read_operand(desc.addr_1, desc.count, desc,
                                   Compression.OP1_COMPRESSED)
            if SPANS.enabled:
                with annotate("accl.stage.reduce", call=desc.span_call):
                    out = _REDUCERS[desc.function](a, b)
            else:
                out = _REDUCERS[desc.function](a, b)
            self._write_result(desc.addr_2, out, desc)
            return 0
        if op == CCLOp.send:
            return self._do_send(desc, comm)
        if op == CCLOp.recv:
            return self._do_recv(desc, comm)
        if op == CCLOp.put:
            return self._do_put(desc, comm)
        if op == CCLOp.get:
            return self._do_get(desc, comm)
        if op in _COLLECTIVES:
            return self._do_collective(desc, comm, handle, defer_launch)
        return int(ErrorCode.COLLECTIVE_NOT_IMPLEMENTED)

    # -- streamed local ops (device-resident port datapath) ----------------
    def _operand_device(self, desc: CallDescriptor, addr: int,
                        which: Compression) -> jax.Array:
        """An operand as a device array: zero-copy for device-resident
        buffers, one H2D for host mirrors."""
        buf = self.dev_bufs.get(addr)
        uncomp = desc.arithcfg.uncompressed_dtype
        if buf is not None and buf.size >= desc.count:
            arr = buf.jax.reshape(-1)[:desc.count]
            return arr.astype(uncomp) if arr.dtype != jnp.dtype(uncomp) \
                else arr
        host = self._read_operand(addr, desc.count, desc, which)
        return jax.device_put(np.array(host, copy=True), self.my_device)

    def _streamed_local(self, desc: CallDescriptor, s_op0: bool,
                        s_res: bool, func) -> int:
        """copy/combine with streamed first operand and/or result: the
        payload stays a device array end to end — port take, (optional)
        on-device arithmetic against op1, port deposit or buffer rebind.
        This is the SURVEY §2.9 mapping of MOVE_STREAM/the bypass port:
        producer and consumer attach at the device-resident ports, and
        the op itself is a fused device program."""
        uncomp = desc.arithcfg.uncompressed_dtype
        # a dtype jax cannot represent with x64 off (int64/f64) must
        # never touch a jnp cast or device_put — both canonicalize to 32
        # bits and silently corrupt the value. The whole datapath stays
        # in numpy for these: port entries host-preserve, arithmetic has
        # a numpy branch, and put_out/_write_result accept host arrays.
        noncanon = _noncanonical(uncomp)
        deadline = (desc.deadline if desc.deadline is not None
                    else time.monotonic() + self.timeout)
        if s_op0:
            data = self.sport.take(desc.count,
                                   None if noncanon else uncomp, deadline)
            if data is None:
                # stalled-stream semantics: same error word as the
                # emulator tiers, nothing consumed
                return int(ErrorCode.KRNL_TIMEOUT_STS_ERROR)
            if noncanon:
                # cast on host from the entries' TRUE dtypes (device
                # entries fetch their exact canonical values; host-
                # preserved entries already carry the full 64 bits)
                data = np.asarray(data).astype(uncomp, copy=False)
        elif noncanon:
            # host read keeps the exact 64-bit operand bits
            data = self._read_operand(desc.addr_0, desc.count, desc,
                                      Compression.OP0_COMPRESSED)
        else:
            data = self._operand_device(desc, desc.addr_0,
                                        Compression.OP0_COMPRESSED)
        if func is not None:
            if isinstance(data, np.ndarray):
                # host-preserved 64-bit entry: arithmetic stays in numpy
                # (jnp would canonicalize both operands to 32 bits and
                # silently corrupt exactly the bits push() preserved)
                from ..emulator.executor import _REDUCERS
                b = self._read_operand(desc.addr_1, desc.count, desc,
                                       Compression.OP1_COMPRESSED)
                data = _REDUCERS[func](data, np.asarray(b, data.dtype))
            else:
                # zero-copy device read for device-resident op1 — the
                # fused datapath must not round-trip it through the host
                b = self._operand_device(desc, desc.addr_1,
                                         Compression.OP1_COMPRESSED)
                data = _COMBINE_JNP[func](data, b)
        if s_res:
            self.sport.put_out(data)
            return 0
        dst = self.dev_bufs.get(desc.addr_2)
        if (dst is not None and dst.size == desc.count and not noncanon
                and not (desc.compression & Compression.RES_COMPRESSED)):
            self._rebind_dev(dst, data)
        else:
            # noncanon results stay on the host write path: _rebind_dev's
            # device_put would canonicalize the 64-bit payload
            self._write_result(desc.addr_2, np.asarray(data), desc)
        return 0

    # -- external-kernel stream ports (Device interface) -------------------
    def push_stream(self, data):
        self.sport.push(data)

    def pop_stream(self, timeout: float = 0.0, count: int | None = None):
        return self.sport.pop(timeout, count)

    # -- send/recv rendezvous ---------------------------------------------
    def _do_send(self, desc: CallDescriptor, comm: Communicator) -> int:
        """Eager send: snapshot the payload onto THIS rank's device and
        park it for the matching recv, which moves it across the fabric
        with a ppermute program (``TpuContext.exchange_transfer``).

        Device-resident sources snapshot zero-copy — jax.Arrays are
        immutable, so holding the reference IS the snapshot (result
        writes rebind, they never mutate). Host-mirror sources pay one
        explicit host copy + H2D, preserving MPI eager semantics (the
        source buffer is reusable the moment send returns)."""
        wire = (desc.arithcfg.compressed_dtype
                if desc.compression & Compression.ETH_COMPRESSED else None)
        if desc.stream_flags & StreamFlags.OP0_STREAM:
            # send-from-stream: the payload comes off the device-resident
            # stream-in port (no buffer, no host staging)
            deadline = (desc.deadline if desc.deadline is not None
                        else time.monotonic() + self.timeout)
            uncomp = np.dtype(desc.arithcfg.uncompressed_dtype)
            if _noncanonical(uncomp):
                # a 64-bit payload cannot cross the device fabric (jax
                # x64 off would truncate it in the exchange program):
                # refuse loudly BEFORE consuming the stream — the
                # emulator tiers carry these, this tier keeps them
                # local-port-only
                return int(ErrorCode.STREAM_NOT_SUPPORTED)
            payload = self.sport.take(desc.count, uncomp, deadline)
            if payload is None:
                return int(ErrorCode.KRNL_TIMEOUT_STS_ERROR)
            if isinstance(payload, np.ndarray):
                # host-preserved entries cast to a canonical dtype by the
                # take land on device here (the gate above guarantees no
                # truncation)
                payload = jax.device_put(payload, self.my_device)
            if wire is not None and payload.dtype != jnp.dtype(wire):
                payload = payload.astype(wire)
        else:
            buf = self.dev_bufs.get(desc.addr_0)
            if (buf is not None and buf.size == desc.count
                    and not (desc.compression & Compression.OP0_COMPRESSED)):
                payload = buf.jax
                if payload.ndim != 1:
                    payload = payload.reshape(-1)
                if wire is not None and payload.dtype != jnp.dtype(wire):
                    payload = payload.astype(wire)  # on-device wire cast
            else:
                host = self._read_operand(desc.addr_0, desc.count, desc,
                                          Compression.OP0_COMPRESSED)
                if wire is not None:
                    host = host.astype(wire)
                # np.array(copy=True): device_put may alias host memory on
                # the CPU backend, and the caller may overwrite the source
                # right after send returns
                payload = jax.device_put(np.array(host, copy=True),
                                         self.my_device)
        if desc.stream_flags & StreamFlags.RES_STREAM:
            # remote-stream send (stream_put): the payload crosses the
            # device fabric and lands on the PEER's stream-in port,
            # bypassing the rx matching queue (strm=1 wire parity,
            # dma_mover.cpp:303) — seqn is NOT consumed
            dst_local = desc.root_src_dst
            peer = self.ctx.devices[
                comm.ranks[dst_local].global_rank]
            if dst_local != comm.local_rank:
                payload = self.ctx.exchange_transfer(
                    comm, payload, comm.local_rank, dst_local)
            if payload.dtype != jnp.dtype(
                    desc.arithcfg.uncompressed_dtype):
                payload = payload.astype(
                    desc.arithcfg.uncompressed_dtype)  # wire decompress
            peer.sport.put_in(payload)
            return 0
        dst_g = comm.ranks[desc.root_src_dst].global_rank
        key = (desc.comm_id, comm.my_global_rank, dst_g)
        ctx = self.ctx
        with ctx._lock:
            if ctx._parked_sends >= ctx.max_parked_sends:
                # eager-buffer exhaustion, not silent HBM retention
                return int(
                    ErrorCode.RECEIVE_OFFCHIP_SPARE_BUFF_OVERFLOW)
            ctx._parked_sends += 1
            ctx._sends[key].append((desc.tag, payload))
            ctx._lock.notify_all()
        return 0

    def _match_send(self, key: tuple, tag: int):
        """Pop the oldest pending send matching ``tag`` (TAG_ANY semantics
        identical to the emulator's RxBufferPool._match). Caller holds the
        ctx lock."""
        from ..constants import TAG_ANY
        pending = self.ctx._sends.get(key)
        if not pending:
            return None
        for i, (stag, payload) in enumerate(pending):
            if tag == TAG_ANY or stag == tag or stag == TAG_ANY:
                del pending[i]
                self.ctx._parked_sends -= 1
                if not pending:
                    del self.ctx._sends[key]
                return payload
        return None

    def _do_recv(self, desc: CallDescriptor, comm: Communicator) -> int:
        src_g = comm.ranks[desc.root_src_dst].global_rank
        me_g = comm.my_global_rank
        key = (desc.comm_id, src_g, me_g)
        deadline = (desc.deadline if desc.deadline is not None
                    else time.monotonic() + self.timeout)
        with self.ctx._lock:
            while True:
                payload = self._match_send(key, desc.tag)
                if payload is not None:
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0 or not self.ctx._lock.wait(remaining):
                    return int(ErrorCode.RECEIVE_TIMEOUT_ERROR)
        if payload.size != desc.count:
            # emulator-tier parity: envelope length must match the posted
            # receive exactly (DMA_MISMATCH_ERROR, executor._fetch)
            return int(ErrorCode.DMA_MISMATCH_ERROR)
        # The host rendezvous above is control plane only (tag matching,
        # MPI ordering); the DATA crosses the device fabric: one ppermute
        # program over the communicator's mesh moves the snapshot from
        # the sender's device to ours (parity: reference send/recv ride
        # the real transport, ccl_offload_control.c:339-380 + rxbuf
        # ingress). Self-sends skip the program — there is no hop.
        src_local = desc.root_src_dst
        me_local = comm.local_rank
        if src_local == me_local:
            received = payload
        else:
            received = self.ctx.exchange_transfer(comm, payload,
                                                  src_local, me_local)
        uncomp = desc.arithcfg.uncompressed_dtype
        if received.dtype != jnp.dtype(uncomp):
            received = received.astype(uncomp)  # wire decompress, on device
        if desc.stream_flags & StreamFlags.RES_STREAM:
            # recv-to-stream: the received device array lands on the
            # local stream-out port (no buffer, no host staging)
            self.sport.put_out(received)
            return 0
        dst = self.dev_bufs.get(desc.addr_2)
        if (dst is not None and dst.size == desc.count
                and not (desc.compression & Compression.RES_COMPRESSED)):
            self._rebind_dev(dst, received)   # stays on device
        else:
            self._write_result(desc.addr_2, np.asarray(received), desc)
        return 0

    # -- one-sided RMA (put/get against registered windows) ----------------
    def _rma_peer(self, desc: CallDescriptor,
                  comm: Communicator) -> "TpuDevice":
        peer = self.ctx.devices[comm.ranks[desc.root_src_dst].global_rank]
        if peer is None:
            raise ACCLError(int(ErrorCode.COMM_NOT_CONFIGURED),
                            "RMA peer rank has no device configured")
        return peer

    def _do_put(self, desc: CallDescriptor, comm: Communicator) -> int:
        """One-sided write: resolve ``(window, byte offset)`` on the
        TARGET rank — which posts no matching call — move the payload
        across, land it. A device-resident window lands through the
        donated ``_window_put_prog`` (in-place update on the target's
        device, no host staging and no second full-size window copy);
        host-mirror windows and byte-misaligned/mixed-dtype ranges take
        the host read-modify-write path."""
        tgt = self._rma_peer(desc, comm)
        uncomp = np.dtype(desc.arithcfg.uncompressed_dtype)
        nbytes = desc.count * uncomp.itemsize
        base = tgt.windows.resolve(desc.tag, desc.addr_1, nbytes)
        wire = (desc.arithcfg.compressed_dtype
                if desc.compression & Compression.ETH_COMPRESSED else None)
        w = tgt.windows.get(desc.tag)
        wbuf = tgt.dev_bufs.get(w.addr)
        boff = base - w.addr   # byte offset inside the window buffer
        if (wbuf is not None and not _noncanonical(uncomp)
                and np.dtype(wbuf.dtype) == uncomp
                and boff % uncomp.itemsize == 0):
            src = self.dev_bufs.get(desc.addr_0)
            if (src is not None and src.size >= desc.count
                    and np.dtype(src.dtype) == uncomp
                    and not (desc.compression
                             & Compression.OP0_COMPRESSED)):
                payload = src.jax.reshape(-1)[:desc.count]  # zero-copy
            else:
                host = self._read_operand(desc.addr_0, desc.count, desc,
                                          Compression.OP0_COMPRESSED)
                payload = jax.device_put(np.array(host, copy=True),
                                         self.my_device)
            if wire is not None:
                payload = payload.astype(wire)   # narrow BEFORE the hop
            if tgt.my_device != self.my_device:
                payload = jax.device_put(payload, tgt.my_device)
            if payload.dtype != jnp.dtype(uncomp):
                payload = payload.astype(uncomp)  # decompress on landing
            wbuf._rebind(_window_put_prog(wbuf.jax, payload,
                                          boff // uncomp.itemsize))
            return 0
        host = self._read_operand(desc.addr_0, desc.count, desc,
                                  Compression.OP0_COMPRESSED)
        if wire is not None:
            host = host.astype(wire).astype(uncomp)  # wire round-trip
        data = np.ascontiguousarray(host, dtype=uncomp).view(np.uint8)
        if wbuf is not None:
            raw = np.asarray(wbuf.jax).reshape(-1).view(np.uint8).copy()
            raw[boff:boff + nbytes] = data
            tgt._rebind_dev(wbuf, raw.view(np.dtype(wbuf.dtype)))
            return 0
        tgt.mem.write(base, host.astype(uncomp, copy=False))
        return 0

    def _do_get(self, desc: CallDescriptor, comm: Communicator) -> int:
        """One-sided read: pull ``count`` elements from byte ``offset``
        of a window on the source rank into the local result buffer (the
        source posts no matching call). Device-resident windows read
        zero-copy and the payload crosses device-to-device."""
        src_dev = self._rma_peer(desc, comm)
        uncomp = np.dtype(desc.arithcfg.uncompressed_dtype)
        nbytes = desc.count * uncomp.itemsize
        base = src_dev.windows.resolve(desc.tag, desc.addr_1, nbytes)
        wire = (desc.arithcfg.compressed_dtype
                if desc.compression & Compression.ETH_COMPRESSED else None)
        w = src_dev.windows.get(desc.tag)
        wbuf = src_dev.dev_bufs.get(w.addr)
        boff = base - w.addr
        if (wbuf is not None and not _noncanonical(uncomp)
                and np.dtype(wbuf.dtype) == uncomp
                and boff % uncomp.itemsize == 0):
            off = boff // uncomp.itemsize
            payload = wbuf.jax.reshape(-1)[off:off + desc.count]
            if wire is not None:
                payload = payload.astype(wire)   # narrow BEFORE the hop
            if src_dev.my_device != self.my_device:
                payload = jax.device_put(payload, self.my_device)
            if payload.dtype != jnp.dtype(uncomp):
                payload = payload.astype(uncomp)
            dst = self.dev_bufs.get(desc.addr_2)
            if (dst is not None and dst.size == desc.count
                    and not (desc.compression
                             & Compression.RES_COMPRESSED)):
                self._rebind_dev(dst, payload)   # stays on device
            else:
                self._write_result(desc.addr_2, np.asarray(payload), desc)
            return 0
        if wbuf is not None:
            raw = np.asarray(wbuf.jax).reshape(-1).view(np.uint8)
            host = np.frombuffer(raw[boff:boff + nbytes].tobytes(), uncomp)
        else:
            host = src_dev.mem.read(base, desc.count, uncomp)
        if wire is not None:
            host = host.astype(wire).astype(uncomp)
        self._write_result(desc.addr_2, host, desc)
        return 0

    # -- collective rendezvous --------------------------------------------
    def _do_collective(self, desc: CallDescriptor, comm: Communicator,
                       handle: CallHandle,
                       defer_launch: bool = False) -> None:
        """Deposit this rank's call; the group-completing arrival launches
        and completes EVERY member's handle directly. No member ever
        blocks a thread waiting for results — once a group is claimed it
        structurally cannot be timed out mid-execution (the round-2 waiter
        bug class), and the only parked state is an incomplete group,
        which the context's deadline sweeper fails with
        RECEIVE_TIMEOUT_ERROR per member (the old per-waiter timeout
        semantics)."""
        # the deposit's parked lifetime is bounded by the CALLER's absolute
        # deadline when one was imposed (call_sync timeout plumbed via the
        # desc, measured from call_sync entry): a collective that timed out
        # for its caller must not be completed later by late-arriving peers
        deadline = (desc.deadline if desc.deadline is not None
                    else time.monotonic() + self.timeout)
        if SPANS.enabled:
            with annotate("accl.deposit", call=desc.span_call) as span:
                key, group, expired, is_last = self._deposit(
                    desc, comm, handle, deadline)
                desc.span_group = f"{key[0]}:{key[1]}"
                span.set(group=desc.span_group)
        else:
            key, group, expired, is_last = self._deposit(
                desc, comm, handle, deadline)
        for h in expired:
            h.complete(int(ErrorCode.RECEIVE_TIMEOUT_ERROR),
                       exception=ACCLError(
                           int(ErrorCode.RECEIVE_TIMEOUT_ERROR),
                           "collective member deadline expired"))
        if not is_last:
            # the synchronous-call path (call_sync/_run_one's caller)
            # blocks in handle.wait(); async callers hold the handle
            return None
        if defer_launch:
            # async last arrival: the heavy launch must not run in the
            # submitter's thread (call_async would block for the whole
            # collective) — hop it to this rank's worker. The inflight
            # slot keeps later same-rank calls FIFO behind it.
            self._inflight_add()
            self._calls.put(lambda: self._finish_group(group, comm))
            return None
        self._finish_group(group, comm)
        return None

    def _deposit(self, desc: CallDescriptor, comm: Communicator,
                 handle: CallHandle, deadline: float):
        """Deposit into the rendezvous slot under the ctx lock: (key,
        group, expired members' handles, whether this arrival completed
        and claimed the group)."""
        ctx = self.ctx
        with ctx._lock:
            # index assignment under the ctx lock: deposit order IS the
            # per-rank matching order (MPI program-order matching)
            idx = self._coll_index[desc.comm_id]
            self._coll_index[desc.comm_id] += 1
            key = (desc.comm_id, idx)
            group = ctx._pending.setdefault(key, {})
            # an expired member must not count toward completion (its
            # caller's wait already raised): fail it here rather than
            # racing the sweeper's next poll — otherwise a late arrival
            # could claim the group and mutate the expired caller's
            # buffers after its timeout. Completion runs OUTSIDE the
            # lock (sweeper discipline): complete() runs done-callbacks
            # synchronously, and one that re-enters the backend would
            # deadlock on the non-reentrant ctx lock.
            now = time.monotonic()
            expired = [group.pop(r)[1]
                       for r in [r for r, (_, _, dl) in group.items()
                                 if dl <= now]]
            group[comm.local_rank] = (desc, handle, deadline)
            is_last = len(group) == comm.size
            if is_last:
                # claim: execution happens OUTSIDE the lock so collectives
                # of disjoint communicators run concurrently
                del ctx._pending[key]
            else:
                ctx._ensure_sweeper()
        return key, group, expired, is_last

    def _finish_group(self, group: dict, comm: Communicator) -> None:
        """Launch a claimed group and complete EVERY member's handle;
        while spans are armed, inside ``accl.launch.<op>`` on the
        launching rank's thread."""
        if SPANS.enabled:
            d = group[comm.local_rank][0]
            with annotate(f"accl.launch.{d.scenario.name}",
                          call=d.span_call, group=d.span_group,
                          nbytes=_payload_bytes(d)):
                self._complete_group(group, comm)
        else:
            self._complete_group(group, comm)

    def _complete_group(self, group: dict, comm: Communicator) -> None:
        err = int(ErrorCode.INVALID_CALL)
        exc_out: BaseException | None = None
        try:
            descs = [group[r][0] for r in range(comm.size)]
            err = self._launch(descs, comm)
        except Exception as exc:  # noqa: BLE001
            # observability: don't bury the cause — attributable to the
            # launching rank, capturable via the accl_tpu logger
            log.error("rank %s: collective group launch failed",
                      getattr(self, "rank", "-"), exc_info=True,
                      extra={"rank": getattr(self, "rank", "-")})
            exc_out = exc
        finally:
            # completion runs in a finally so a claimed group ALWAYS
            # resolves — any escape path (desc-assembly errors,
            # BaseExceptions) that skipped it would wedge every waiter
            for _, h, _dl in group.values():
                h.complete(err, exception=exc_out)

    def _launch(self, descs: list, comm: Communicator) -> int:
        """Execute one collective for all member ranks (no locks held)."""
        ctx = self.ctx
        d0 = descs[0]
        op = d0.scenario
        if any(d.scenario != op or d.count != d0.count for d in descs):
            return int(ErrorCode.INVALID_CALL)
        count = d0.count
        W = comm.size
        cfg = d0.arithcfg
        wire = (cfg.compressed_dtype
                if d0.compression & Compression.ETH_COMPRESSED else None)
        devs = [ctx.devices[comm.ranks[r].global_rank] for r in range(W)]

        def read_all(addr_of, n):
            rows = []
            for r, d in enumerate(descs):
                addr = addr_of(d)
                if addr:
                    rows.append(devs[r]._read_operand(
                        addr, n, d, Compression.OP0_COMPRESSED))
                else:
                    rows.append(np.zeros(n, cfg.uncompressed_dtype))
            return rows

        coll, alg = ctx.coll_for(comm), ctx.algorithm
        # per-call selector (CollectiveAlgorithm) overrides the context
        # default: ring variants lower to the shard_map ppermute rings,
        # everything else to XLA's native collectives. Validation uses the
        # same table as the emulator tiers so invalid (op, algorithm) pairs
        # fail identically everywhere.
        try:
            check_algorithm(op.name, d0.algorithm)
        except ValueError:
            return int(ErrorCode.INVALID_CALL)
        if d0.algorithm in (CollectiveAlgorithm.RING,
                            CollectiveAlgorithm.FUSED_RING):
            alg = "ring"
        elif d0.algorithm != CollectiveAlgorithm.AUTO:
            alg = "xla"
        # block-scaled quantized wire (compress_dtype=..., block_scale=True
        # at the driver): the dense ring collectives take the fused Pallas
        # quantize->combine->requant lane — qblock selects it and pins the
        # ppermute ring (the only shape the fused codec hops ride). Other
        # ops fall back to the FULL-PRECISION wire: their per-tensor cast
        # lanes would silently truncate (int8) or re-scale per tensor
        # (fp8), neither of which is block-scaled semantics.
        qblock = 0
        if wire is not None and d0.compression & Compression.BLOCK_SCALED:
            from ..quant import DEFAULT_BLOCK
            from ..parallel.collectives import BS_WIRE_DTYPE_NAMES
            if (op in (CCLOp.allreduce, CCLOp.reduce_scatter,
                       CCLOp.allgather)
                    and _wire_name(wire) in BS_WIRE_DTYPE_NAMES):
                qblock = int(getattr(cfg, "quant_block", 0)
                             or DEFAULT_BLOCK)
                alg = "ring"
            else:
                wire = None
        # rooted ops default to the hierarchical 2D-mesh tree when the comm
        # has 2D structure — O(outer+inner) hop fan-out instead of the
        # psum/all_gather-class traffic of the masked 1-D lowerings (which
        # cost allreduce/allgather bandwidth regardless of root). Explicit
        # ROUND_ROBIN/RING selectors keep the 1-D path; the explicit TREE
        # selector (legal for bcast/gather/reduce, VALID_ALGORITHMS) pins
        # the tree — scatter reaches it via AUTO only. Rooted reduce
        # rides the tree only uncompressed: the tree has no
        # wire-compression lanes, and the compressed 1-D path's
        # decompress-before-arith numerics must win.
        rooted = (CCLOp.bcast, CCLOp.scatter, CCLOp.gather, CCLOp.reduce)
        use_tree = (op in rooted
                    and (d0.algorithm == CollectiveAlgorithm.AUTO
                         or (d0.algorithm == CollectiveAlgorithm.TREE
                             and op in (CCLOp.bcast, CCLOp.gather,
                                        CCLOp.reduce)))
                    and not (op == CCLOp.reduce and wire is not None))
        tree = ctx.tree_for(comm) if use_tree else None
        root = d0.root_src_dst
        if op == CCLOp.barrier:
            return 0  # rendezvous above IS the barrier

        # -- device-resident fast path (to_from_fpga=False parity) --------
        # When every member rank's src AND dst buffer is device-resident
        # with exact geometry, the dense collectives skip host staging
        # entirely: per-rank arrays assemble into the flat global via
        # make_array_from_single_device_arrays, one cached program runs,
        # and result shards rebind each rank's dst — zero host copies.
        dense_fast = {CCLOp.allreduce: (count, count),
                      CCLOp.allgather: (count, W * count),
                      CCLOp.reduce_scatter: (W * count, count),
                      CCLOp.alltoall: (W * count, W * count)}
        if op in dense_fast:
            n_in, n_out = dense_fast[op]
            res = self._launch_device_fast(op, descs, devs, coll, alg,
                                           wire, cfg, n_in, n_out, d0,
                                           qblock)
            if res is not None:
                return res
        if op in rooted:
            res = self._launch_device_rooted(op, descs, devs, coll, alg,
                                             cfg, count, root, d0, wire)
            if res is not None:
                return res

        if op == CCLOp.allreduce:
            x = coll.shard(read_all(lambda d: d.addr_0, count))
            out = np.asarray(coll.allreduce(x, func=d0.function,
                                            algorithm=alg, wire_dtype=wire,
                                            qblock=qblock))
            for r, d in enumerate(descs):
                devs[r]._write_result(d.addr_2, out[r], d)
            return 0
        if op == CCLOp.reduce:
            rows = read_all(lambda d: d.addr_0, count)
            if tree is not None:
                out = np.asarray(tree.reduce(tree.shard(rows), root=root,
                                             func=d0.function))
            else:
                out = np.asarray(coll.reduce(coll.shard(rows), root=root,
                                             func=d0.function,
                                             wire_dtype=wire))
            devs[root]._write_result(descs[root].addr_2, out[root],
                                     descs[root])
            return 0
        if op == CCLOp.reduce_scatter:
            x = coll.shard(read_all(lambda d: d.addr_0, W * count))
            out = np.asarray(coll.reduce_scatter(x, func=d0.function,
                                                 algorithm=alg,
                                                 wire_dtype=wire,
                                                 qblock=qblock))
            for r, d in enumerate(descs):
                devs[r]._write_result(d.addr_2, out[r], d)
            return 0
        if op == CCLOp.allgather:
            x = coll.shard(read_all(lambda d: d.addr_0, count))
            out = np.asarray(coll.allgather(x, algorithm=alg,
                                            wire_dtype=wire, qblock=qblock))
            for r, d in enumerate(descs):
                devs[r]._write_result(d.addr_2, out[r], d)
            return 0
        if op == CCLOp.bcast:
            rows = read_all(lambda d: d.addr_0, count)
            if tree is not None:
                out = np.asarray(tree.bcast(tree.shard(rows), root=root,
                                            wire_dtype=wire))
            else:
                out = np.asarray(coll.bcast(coll.shard(rows), root=root,
                                            wire_dtype=wire))
            for r, d in enumerate(descs):
                if r != root:  # root's own buffer never crossed the wire
                    devs[r]._write_result(d.addr_0, out[r], d)
            return 0
        if op == CCLOp.scatter:
            rows = read_all(lambda d: d.addr_0, W * count)
            if tree is not None:
                out = np.asarray(tree.scatter(tree.shard(rows), root=root,
                                              wire_dtype=wire))
            else:
                out = np.asarray(coll.scatter(coll.shard(rows), root=root,
                                              wire_dtype=wire))
            for r, d in enumerate(descs):
                devs[r]._write_result(d.addr_2, out[r], d)
            return 0
        if op == CCLOp.gather:
            rows = read_all(lambda d: d.addr_0, count)
            if tree is not None:
                out = np.asarray(tree.gather(tree.shard(rows), root=root,
                                             wire_dtype=wire))
            else:
                out = np.asarray(coll.gather(coll.shard(rows), root=root,
                                             wire_dtype=wire))
            devs[root]._write_result(descs[root].addr_2, out[root],
                                     descs[root])
            return 0
        if op == CCLOp.alltoall:
            x = coll.shard(read_all(lambda d: d.addr_0, W * count))
            # the program casts chunks on the wire and restores each
            # rank's self chunk exact (emulator-tier wire_q_except parity)
            out = np.asarray(coll.alltoall(x, wire_dtype=wire))
            for r, d in enumerate(descs):
                devs[r]._write_result(d.addr_2, out[r], d)
            return 0
        return int(ErrorCode.COLLECTIVE_NOT_IMPLEMENTED)

    def _launch_device_fast(self, op, descs, devs, coll, alg, wire, cfg,
                            n_in: int, n_out: int, d0,
                            qblock: int = 0) -> int | None:
        """Zero-host-staging dense collective. Returns None when any
        member's operands disqualify (not device-resident, geometry or
        dtype mismatch, host-side compression flags) — the caller then
        takes the staged path. OP0/RES_COMPRESSED disqualify because a
        device buffer has one storage dtype (no compressed host mirror);
        ETH (wire) compression stays eligible — it lives inside the
        program."""
        bad = (Compression.OP0_COMPRESSED | Compression.OP1_COMPRESSED
               | Compression.RES_COMPRESSED)
        uncomp = np.dtype(cfg.uncompressed_dtype)
        srcs, dsts = [], []
        for r, d in enumerate(descs):
            if d.compression & bad:
                return None
            sb = devs[r].dev_bufs.get(d.addr_0)
            db = devs[r].dev_bufs.get(d.addr_2)
            if (sb is None or db is None
                    or sb.size != n_in or db.size != n_out
                    or sb.dtype != uncomp or db.dtype != uncomp):
                return None
            srcs.append(sb.jax if sb.jax.ndim == 1 else sb.jax.reshape(-1))
            dsts.append(db)
        func = (d0.function if op in (CCLOp.allreduce, CCLOp.reduce_scatter)
                else ReduceFunc.SUM)
        self._run_flat(coll, srcs, coll._program_flat(
            op.name, alg, func, _wire_name(wire), None, qblock),
            dict(enumerate(dsts)), devs, descs)
        return 0

    def _run_flat(self, coll, srcs: list, program, dst_map: dict, devs,
                  descs) -> None:
        """The device-resident launch: assemble the flat operand from the
        ranks' arrays, dispatch ``program`` on it, rebind the result
        shards — spanned accl.assemble / accl.dispatch / accl.rebind
        while SPANS is armed."""
        if not SPANS.enabled:
            out = program(self.ctx.assemble_flat(coll, srcs))
            self._rebind_out_shards(coll, out, dst_map, devs)
            return
        call = descs[devs.index(self)].span_call
        with annotate("accl.assemble", call=call):
            x = self.ctx.assemble_flat(coll, srcs)
        with annotate("accl.dispatch", call=call):
            out = program(x)
        with annotate("accl.rebind", call=call):
            self._rebind_out_shards(coll, out, dst_map, devs)

    def _rebind_out_shards(self, coll, out, dst_map: dict, devs):
        """Rebind a flat program output's per-rank shards onto the
        destination device buffers in ``dst_map`` (rank -> buffer; ranks
        absent from the map — e.g. non-roots of a gather — are dropped
        without touching any buffer).

        Shard objects are expensive to build (index/device per shard,
        ~15us each); the position->rank order is a pure function of the
        (fixed) flat sharding, so compute it once per mesh and reuse.
        jax.Array._arrays is private, so the first call also VERIFIES it
        matches addressable_shards device-for-device before trusting it
        on later calls — if the contract ever changes (or the attribute
        disappears) we stay on the public API instead of silently
        scattering results to the wrong ranks."""
        order = coll._cache.get("shard_order")
        if order is None:
            shards = list(out.addressable_shards)
            order = [(s.index[0].start or 0) * len(shards)
                     // out.shape[0] for s in shards]
            coll._cache["shard_order"] = order
            arrs = getattr(out, "_arrays", None)
            coll._cache["shard_arrays_ok"] = bool(
                arrs is not None and len(arrs) == len(shards)
                and all(getattr(a, "device", None) == s.device
                        for a, s in zip(arrs, shards)))
            datas = [s.data for s in shards]
        elif coll._cache.get("shard_arrays_ok"):
            datas = out._arrays
        else:
            datas = [s.data for s in out.addressable_shards]
        for pos, r in enumerate(order):
            db = dst_map.get(r)
            if db is None:
                continue
            # eligibility proved size+dtype; only a non-1-D dst needs the
            # general rebind (reshape), so the common case is one pointer
            # swap
            if len(db._shape) == 1:
                db._rebind(datas[pos])
            else:
                devs[r]._rebind_dev(db, datas[pos])

    def _launch_device_rooted(self, op, descs, devs, coll, alg, cfg,
                              count: int, root: int, d0,
                              wire=None) -> int | None:
        """Zero-host-staging ROOTED collective (bcast/scatter/gather/
        reduce) — the reference's ``to_from_fpga=False`` mode applies to
        every op, not just the dense four (VERDICT r4 item 3). Buffer
        geometry is asymmetric: only the ranks that own data on each
        side must be device-resident; a scatter's non-root "sources"
        don't exist and ride in as cached device zeros. Returns None
        when the involved buffers disqualify (caller takes the staged
        path). ETH (wire) compression rides inside the program, like
        the dense fast path."""
        bad = (Compression.OP0_COMPRESSED | Compression.OP1_COMPRESSED
               | Compression.RES_COMPRESSED)
        if any(d.compression & bad for d in descs):
            return None
        uncomp = np.dtype(cfg.uncompressed_dtype)
        W = len(descs)

        def resident(r, addr, n):
            """Device buffer at (rank, addr) with exact geometry, else
            None (disqualifies)."""
            b = devs[r].dev_bufs.get(addr)
            if b is None or b.size != n or b.dtype != uncomp:
                return None
            return b

        def flat(b):
            return b.jax if b.jax.ndim == 1 else b.jax.reshape(-1)

        if op == CCLOp.bcast:
            # in-place on addr_0 everywhere: root's is the source, every
            # other rank's is the destination
            bufs = [resident(r, d.addr_0, count)
                    for r, d in enumerate(descs)]
            if any(b is None for b in bufs):
                return None
            srcs = [flat(b) for b in bufs]
            dst_map = {r: b for r, b in enumerate(bufs) if r != root}
        elif op == CCLOp.reduce:
            bufs = [resident(r, d.addr_0, count)
                    for r, d in enumerate(descs)]
            rootdst = resident(root, descs[root].addr_2, count)
            if any(b is None for b in bufs) or rootdst is None:
                return None
            srcs = [flat(b) for b in bufs]
            dst_map = {root: rootdst}
        elif op == CCLOp.scatter:
            rootsrc = resident(root, descs[root].addr_0, W * count)
            dsts = [resident(r, d.addr_2, count)
                    for r, d in enumerate(descs)]
            if rootsrc is None or any(b is None for b in dsts):
                return None
            # non-root input shards are never read by the binomial
            # schedule's first hop from root; cached device zeros keep
            # the flat assembly uniform without host traffic
            srcs = [flat(rootsrc) if r == root
                    else self.ctx.zero_shard(coll.device_list[r],
                                             W * count, uncomp)
                    for r in range(W)]
            dst_map = dict(enumerate(dsts))
        elif op == CCLOp.gather:
            bufs = [resident(r, d.addr_0, count)
                    for r, d in enumerate(descs)]
            rootdst = resident(root, descs[root].addr_2, W * count)
            if any(b is None for b in bufs) or rootdst is None:
                return None
            srcs = [flat(b) for b in bufs]
            dst_map = {root: rootdst}
        else:
            return None

        func = d0.function if op == CCLOp.reduce else ReduceFunc.SUM
        self._run_flat(coll, srcs, coll._program_flat(
            op.name, alg, func, _wire_name(wire), root), dst_map, devs, descs)
        return 0


def tpu_world(world_size: int | None = None, platform: str | None = None,
              algorithm: str = "xla", timeout: float = DEFAULT_TIMEOUT_S,
              tuner=None) -> list:
    """Create ACCL instances backed by a device mesh (one rank per device).

    The TPU-tier analog of testing.emu_world. ``tuner`` (one shared
    :class:`~accl_tpu.tuner.Tuner`) resolves AUTO selectors by
    size/topology — same rank-agreement rule as emu_world."""
    from ..accl import ACCL
    from ..communicator import Communicator, Rank
    ctx = TpuContext(world_size, platform=platform, algorithm=algorithm)
    W = ctx.world_size
    accls = []
    for r in range(W):
        comm = Communicator(ranks=[Rank() for _ in range(W)], local_rank=r)
        accls.append(ACCL(ctx.device(r), comm, timeout=timeout,
                          tuner=tuner))
    return accls
