"""Abstract device backend interface.

Parity: the reference driver's device abstraction is the MMIO+call transport
pair — real hardware (pynq Overlay + hostctrl kernel) or SimDevice (ZMQ) —
behind one ``call/start/read/write`` surface (driver/pynq/accl.py:33-159).
Ours is a clean ABC the driver talks to; buffers and call descriptors are
the currency.
"""

from __future__ import annotations

import abc
import dataclasses
import threading
import time
from typing import Sequence

from ..buffer import ACCLBuffer
from ..call import CallDescriptor, CallHandle
from ..communicator import Communicator
from ..tracing import health_rows


def _device_metrics_rows(dev: "Device"):
    """Shared metrics collector for one rank's backend: reports whichever
    health surfaces the backend actually has (rx pool, move executor,
    plan cache) as registry rows — one mapping (:func:`tracing.health_rows`,
    shared with the daemon collector) for every Device subclass so
    backends can never drift in how they report. Polled only at snapshot
    time (:meth:`~accl_tpu.tracing.MetricsRegistry.snapshot`)."""
    # "tier" disambiguates from _daemon_metrics_rows' identical families:
    # one process can host an in-process device world AND spawn_world
    # daemons, and {rank} alone would merge their series (last-write-wins
    # gauges, summed counters) into one indistinguishable key
    labels = {"rank": getattr(dev, "_metrics_rank", -1), "tier": "device"}
    # world tag (emu backends): rank+tier alone would merge two
    # concurrently live same-shape worlds' series — counters would sum,
    # gauges would last-write-win. Shares the fabric's ctx_seq so device,
    # driver and fabric rows of one world carry the same tag.
    ctx_seq = getattr(getattr(getattr(dev, "ctx", None), "fabric", None),
                      "ctx_seq", None)
    if ctx_seq is not None:
        labels["ctx"] = ctx_seq
    yield from health_rows(dev, labels)


class Device(abc.ABC):
    """One rank's execution backend."""

    # Optional attached Tuner (accl_tpu/tuner): the driver sets this when
    # constructed with ``tuner=`` so engine-level AUTO resolution
    # (moveengine.expand_call via MoveContext.tuner) can consult it for
    # descriptors that still carry AUTO when they reach the engine.
    tuner = None

    def register_metrics(self, rank: int):
        """Attach this backend to the process-wide metrics registry
        (weakly — the collector dies with the device). Backends call it
        once they own their pool/executor/plan-cache surfaces."""
        from ..tracing import METRICS
        self._metrics_rank = rank
        METRICS.register_collector(self, _device_metrics_rows)

    def topology(self):
        """Link-level descriptor of this backend's fabric tier, feeding
        the tuner's cost model (tuner/cost.py). Backends override with
        calibrated per-tier figures; this generic default only has to
        order algorithms sanely."""
        from ..tuner.cost import Topology
        return Topology(world_size=0, alpha_us=50.0, beta_gbps=1.0,
                        tier="generic")

    def auto_resolvable_ops(self):
        """Ops whose AUTO the driver may resolve through the tuner before
        issue; None (the default) means every op with an algorithm axis.
        A backend that keeps some ops' AUTO for itself restricts this —
        the TPU tier keeps the rooted ops', whose programs a tuner's
        choice from move-engine cost models would not improve
        (device/tpu.py overrides)."""
        return None

    # -- shared inline fast-path gate (used by Emu/Sim backends) ----------
    # A backend that can retire a synchronous call in the caller's thread
    # guards the path with one counter: >0 means calls are queued or not
    # yet past the point that fixes their submission order (each backend
    # documents where it decrements). The gate is shared so the
    # concurrency-sensitive pattern exists once.

    # class-level guard: creation of the per-instance gate must itself be
    # race-free (two first-callers racing the lazy init would each build a
    # lock and lose an increment)
    _inline_init_mu = threading.Lock()

    def _inline_state(self):
        mu = getattr(self, "_inline_mu", None)
        if mu is None:
            with Device._inline_init_mu:
                mu = getattr(self, "_inline_mu", None)
                if mu is None:
                    self._inline_inflight = 0
                    mu = self._inline_mu = threading.Lock()
        return mu

    def _inline_begin(self, waitfor: Sequence[CallHandle]) -> bool:
        """True iff the device is idle and every dependency retired —
        the caller may run inline and MUST call :meth:`_inflight_done`
        when finished."""
        if not all(dep.done() for dep in waitfor):
            return False
        with self._inline_state():
            if self._inline_inflight != 0:
                return False
            self._inline_inflight += 1
            return True

    def _inflight_add(self):
        with self._inline_state():
            self._inline_inflight += 1

    def _inflight_done(self):
        with self._inline_state():
            self._inline_inflight -= 1

    @abc.abstractmethod
    def register_buffer(self, buf: ACCLBuffer): ...

    @abc.abstractmethod
    def deregister_buffer(self, buf: ACCLBuffer): ...

    def sync_to_device(self, buf: ACCLBuffer):
        """Host->device copy; default no-op for host-memory backends."""

    def sync_from_device(self, buf: ACCLBuffer):
        """Device->host copy; default no-op for host-memory backends."""

    @abc.abstractmethod
    def call_async(self, desc: CallDescriptor,
                   waitfor: Sequence[CallHandle] = (), *,
                   inline_ok: bool = False) -> CallHandle:
        """Submit a call; returns its handle.

        ``inline_ok`` is a latency hint: the caller will immediately block
        on the handle (a synchronous driver call), so a backend MAY retire
        the call in the calling thread instead of a worker. It must never
        be set for calls the caller treats as asynchronous — an inline
        blocking recv would stall (or deadlock) a symmetric async program.
        """

    def call_sync(self, desc: CallDescriptor,
                  waitfor: Sequence[CallHandle] = (),
                  timeout: float | None = None):
        # inline retirement blocks inside call_async and would bypass a
        # local timeout bound, so only hint inline when none is imposed
        if timeout is not None:
            # plumb the caller's bound into the descriptor as an ABSOLUTE
            # deadline (from this moment — queue or dependency delay must
            # not extend it) so backend rendezvous deadlines (TPU-tier
            # deposits) honor it: a TimeoutError here must imply the call
            # will not run later
            desc = dataclasses.replace(
                desc, deadline=time.monotonic() + timeout)
        return self.call_async(desc, waitfor,
                               inline_ok=timeout is None).wait(timeout)

    @abc.abstractmethod
    def configure_communicator(self, comm: Communicator,
                               tenant: str | None = None):
        """Register a communicator. ``tenant`` optionally groups it under
        a multi-tenant service tenant (accl_tpu/service) — backends
        without a service layer may ignore it, but must accept it."""

    @abc.abstractmethod
    def set_timeout(self, timeout: float): ...

    @abc.abstractmethod
    def set_max_segment_size(self, nbytes: int): ...

    def preferred_segment_size(self) -> int:
        """Largest segment this backend can accept; the driver defaults the
        max segment size to this at init (reference: the driver sets
        max_segment_size = rx bufsize at bring-up, accl.py:380)."""
        from ..constants import DEFAULT_MAX_SEGMENT_SIZE
        return DEFAULT_MAX_SEGMENT_SIZE

    # -- external-kernel stream ports --------------------------------------
    def push_stream(self, data):
        """Feed the rank's stream-in port (OP0_STREAM operand source;
        reference: the external-kernel AXIS port, SWITCH_M_BYPASS).
        Backends without a stream port raise STREAM_NOT_SUPPORTED — never
        silently ignore the flag."""
        from ..constants import ACCLError, ErrorCode
        raise ACCLError(int(ErrorCode.STREAM_NOT_SUPPORTED),
                        f"{type(self).__name__} has no stream port; fuse "
                        "producers into the device program instead")

    def pop_stream(self, timeout: float = 0.0, count: int | None = None):
        """Read from the stream-out port: ``count`` elements, or the next
        produced entry whole when ``count`` is None (RES_STREAM sink)."""
        from ..constants import ACCLError, ErrorCode
        raise ACCLError(int(ErrorCode.STREAM_NOT_SUPPORTED),
                        f"{type(self).__name__} has no stream port")

    # -- device-resident buffers (to_from_fpga=False fast path) ------------
    def adopt_device_array(self, arr):
        """Accept a live device array for a device-resident buffer.
        Backends without device arrays reject — never silently fall back
        to a host mirror the caller believes is zero-copy."""
        raise ValueError(
            f"{type(self).__name__} has no device-array storage; use a "
            "host buffer (device-resident mode is a TPU-backend feature)")

    def make_device_array(self, shape, dtype, init=None):
        """Allocate a fresh device array on this rank's device (zeros, or
        ``init`` contents) for a device-resident buffer."""
        raise ValueError(
            f"{type(self).__name__} has no device-array storage; use a "
            "host buffer (device-resident mode is a TPU-backend feature)")

    # -- one-sided RMA windows (accl_tpu/rma) ------------------------------
    def register_window(self, wid: int, addr: int, nbytes: int):
        """Register ``[addr, addr+nbytes)`` as one-sided window ``wid``
        so peers can put/get against it. Backends without an RMA engine
        reject — a put toward an unregistered tier must fail at
        registration time, not as a mystery timeout."""
        from ..constants import ACCLError, ErrorCode
        raise ACCLError(int(ErrorCode.COLLECTIVE_NOT_IMPLEMENTED),
                        f"{type(self).__name__} has no one-sided RMA "
                        "engine (emulator/daemon tiers only)")

    def deregister_window(self, wid: int):
        """Remove a window registration (no-op when absent)."""

    def poll_notifications(self, window: int, max_records: int = 64):
        """Drain put-with-notify completion records for ``window``
        (``rma.notify.ANY_WINDOW`` = all). Must be purely local — no
        wire traffic, no collective. Backends without an RMA engine
        simply have nothing pending."""
        return []

    # -- elastic membership (ACCL.grow_communicator) -----------------------
    def join_handshake(self, comm: Communicator, timeout: float) -> int:
        """Bootstrap handshake of a grown communicator: block until every
        member of ``comm`` has announced itself alive and agreeing on the
        membership, or ``timeout`` expires. Returns 0 on success or a
        typed error word (JOIN_FAILED, OR-ed with RECEIVE_TIMEOUT_ERROR
        on a plain timeout). Single-controller backends (TPU mesh tier)
        have no independent peers to synchronize with — membership is a
        host-side fact there — so the default is immediate success; the
        emulator and daemon tiers exchange JOIN_STRM hello frames."""
        return 0

    def abort_comm(self, comm_id: int, err: int):
        """Containment hook for an application-driven revoke: abort
        in-flight programs on ``comm_id`` with the typed error NOW and
        latch it for pending recvs, instead of letting async handles
        ride out their full receive deadline. Default no-op (backends
        without an abortable executor surface the revocation at the
        next call through the driver's revoked-comm check)."""

    def soft_reset(self):
        """Parity: HOUSEKEEP_SWRST (ccl_offload_control.c:1244-1247)."""

    def deinit(self):
        """Release backend resources (driver deinit, accl.py:421-433)."""

    # -- runtime config calls ----------------------------------------------
    def segment_size_bound(self) -> int | None:
        """Upper bound a config call may set the segment size to; None =
        unbounded (the emulator bounds it by its rx buffer size, mirroring
        segments-must-fit-spare-buffers, reference accl.py:660-667)."""
        return None

    def apply_config(self, desc: CallDescriptor) -> int:
        """Shared ACCL_CONFIG dispatch for in-process backends
        (c:1240-1283): subfunction in ``tag``, value in ``count`` (ms for
        timeout, bytes for segment size). The in-process fabrics have no
        ports/sessions/stack to manage, so the connection subfunctions
        succeed as no-ops — like the reference's loopback builds where the
        dummy stack always accepts. The socket daemons implement the full
        surface (emulator/daemon.py, native/cclo_emud.cpp)."""
        from ..constants import CfgFunc, ErrorCode
        try:
            fn = CfgFunc(desc.tag)
        except ValueError:
            return int(ErrorCode.INVALID_CALL)
        val = int(desc.count)
        if fn == CfgFunc.reset_periph:
            self.soft_reset()
            return 0
        if fn == CfgFunc.set_timeout:
            self.set_timeout(val / 1000.0)
            return 0
        if fn == CfgFunc.set_max_segment_size:
            bound = self.segment_size_bound()
            if bound is not None and val > bound:
                return int(ErrorCode.DMA_SIZE_ERROR)
            self.max_segment_size = val
            return 0
        if fn == CfgFunc.start_profiling:
            self.profiling = True
            return 0
        if fn == CfgFunc.end_profiling:
            self.profiling = False
            return 0
        if fn in (CfgFunc.enable_pkt, CfgFunc.open_port, CfgFunc.open_con,
                  CfgFunc.close_con, CfgFunc.set_stack_type):
            return 0
        return int(ErrorCode.INVALID_CALL)
