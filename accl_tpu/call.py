"""Call descriptors and asynchronous call handles.

Parity: the reference host issues a 15-word call descriptor
{scenario, count, comm, root_src_dst, function, tag, arithcfg,
compression_flags, stream_flags, addr_0/1/2 (lo+hi)} to the CCLO
(driver/pynq/accl.py:594-602; kernels/plugins/hostctrl/hostctrl.cpp:25-91),
and gets back one status word. ``call_async`` returns a handle the host can
chain via ``waitfor=`` (ap_ctrl_chain async chaining, accl.py:594-597).

TPU-native design: the descriptor is a dataclass (no MMIO marshalling), and
the handle wraps either a concurrent future (emulator backend) or JAX's
async dispatch (TPU backend — dispatch is already asynchronous; ``wait``
is ``jax.block_until_ready``). ``waitfor=`` chaining is preserved: a backend
starts a call only after its dependencies complete.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Sequence

from .constants import (ACCLError, CCLOp, CollectiveAlgorithm, Compression,
                        ErrorCode, ReduceFunc, StreamFlags)


@dataclasses.dataclass
class CallDescriptor:
    """One device call. Field-for-field capability match of the reference's
    15-word descriptor (accl.py:594-602)."""

    scenario: CCLOp
    count: int = 0
    comm_id: int = 0
    root_src_dst: int = 0
    function: ReduceFunc = ReduceFunc.SUM
    tag: int = 0
    arithcfg: Any = None                      # resolved ArithConfig
    compression: Compression = Compression.NONE
    stream_flags: StreamFlags = StreamFlags.NO_STREAM
    algorithm: CollectiveAlgorithm = CollectiveAlgorithm.AUTO
    addr_0: Any = None                        # op0 buffer / array
    addr_1: Any = None                        # op1 buffer / array
    addr_2: Any = None                        # result buffer / array
    # Caller-visible ABSOLUTE deadline (time.monotonic() seconds), set by
    # Device.call_sync at entry — so queue/dependency delay before the
    # backend examines the call cannot extend it. Host-side only (never
    # crosses the wire). Backends with parked rendezvous state (TPU tier
    # deposits) bound that state's lifetime by this, so a call that timed
    # out for the caller cannot later be completed by late peers and
    # mutate the caller's buffers.
    deadline: Any = None
    # alltoallv count vectors: (tuple(send_counts), tuple(recv_counts)),
    # world_size elements each, in ELEMENTS of the uncompressed dtype.
    # None for every fixed-count scenario. ``count`` is set to
    # max(sum(send), sum(recv)) so size bounds hold without special cases.
    counts: Any = None
    # Cross-call pipelining hint (the C++ driver's call_chain analog): the
    # caller asserts this async call's buffers are disjoint from the
    # still-draining predecessor's, so a backend MAY admit its move
    # program into the streamed executor while the predecessor drains.
    # Per-peer wire emission stays in global program order (the egress
    # ordering domain extends across the chain) and handles still
    # complete in submission order; a failed link aborts its successors.
    # Backends without cross-call pipelining ignore the hint.
    chain: bool = False
    # Span identifiers (accl_tpu.tracing.SPANS), host-side only and set
    # only while spans are armed: the per-process id every span of this
    # call carries as ``call``, and the rendezvous slot
    # ("<comm_id>:<index>") a collective deposited into, which the spans
    # of every member of that collective carry as ``group``.
    span_call: int = 0
    span_group: str = ""


class CallHandle:
    """Future-like handle for an async device call.

    ``wait()`` blocks until the call retires and raises :class:`ACCLError`
    on a nonzero error word (check_return_value parity, accl.py:617-624).
    Handles compose: pass them via ``waitfor=`` to chain calls.
    """

    def __init__(self, context: str = ""):
        self._done = threading.Event()
        self._error_word = 0
        self._result: Any = None
        self._exception: BaseException | None = None
        self._callbacks: list = []
        self._cb_lock = threading.Lock()
        self.context = context

    # backend side -----------------------------------------------------
    def complete(self, error_word: int = 0, result: Any = None,
                 exception: BaseException | None = None):
        self._error_word = int(error_word)
        self._result = result
        self._exception = exception
        # run callbacks BEFORE waking waiters: a host thread returning from
        # wait() must observe every observer effect (e.g. profiler records)
        with self._cb_lock:
            callbacks, self._callbacks = self._callbacks, []
        for cb in callbacks:
            try:
                cb(self._error_word)
            except Exception:  # noqa: BLE001 — a raising observer must not
                pass           # re-enter the backend worker / double-complete
        self._done.set()

    def add_done_callback(self, fn):
        """Invoke ``fn(error_word)`` when the call retires (immediately if
        already retired). Used by the tracing subsystem to attribute an
        async chained call its issue -> retire host time."""
        with self._cb_lock:
            if not self._done.is_set():
                self._callbacks.append(fn)
                return
        try:
            fn(self._error_word)
        except Exception:  # noqa: BLE001
            pass

    # host side --------------------------------------------------------
    def wait(self, timeout: float | None = None) -> Any:
        if not self._done.wait(timeout):
            raise TimeoutError(f"call {self.context or ''} did not complete "
                               f"within {timeout}s")
        if self._error_word != int(ErrorCode.COLLECTIVE_OP_SUCCESS):
            # chain the backend's underlying exception for debuggability
            raise ACCLError(self._error_word, self.context) from self._exception
        return self._result

    def done(self) -> bool:
        return self._done.is_set()

    @property
    def error_word(self) -> int:
        return self._error_word


class _AlwaysSet:
    """Event stand-in for already-retired handles (no lock allocation:
    a CompletedHandle is built for EVERY synchronous call, and the
    Event+lock pair showed up in the sim-tier latency profile)."""

    @staticmethod
    def wait(timeout=None) -> bool:
        return True

    @staticmethod
    def is_set() -> bool:
        return True

    @staticmethod
    def set():
        pass


_ALWAYS_SET = _AlwaysSet()
_SHARED_CB_LOCK = threading.Lock()  # uncontended: callbacks of retired
#                                     handles run immediately


class CompletedHandle(CallHandle):
    """A handle for synchronously-executed calls (already retired)."""

    def __init__(self, error_word: int = 0, result: Any = None,
                 context: str = ""):
        self._done = _ALWAYS_SET
        self._error_word = int(error_word)
        self._result = result
        self._exception = None
        self._callbacks: list = []
        self._cb_lock = _SHARED_CB_LOCK
        self.context = context


def wait_all(handles: Sequence[CallHandle], timeout: float | None = None):
    """Wait on a set of chained handles; first error wins."""
    results = []
    for h in handles:
        results.append(h.wait(timeout))
    return results
