"""Tracing / profiling subsystem.

The reference has no software tracer — its profiling surface is (a) the
``nop`` op for call-latency probes (driver/pynq/accl.py:738-745), (b) the
chained-async benchmark harness writing CSVs (test/host/test.py:923-1156),
(c) ``start_profiling/end_profiling`` config calls in the older XRT driver
(driver/xrt/include/xlnx-consts.hpp:27-28), and (d) hardware ILA insertion
scripts (kernels/cclo/tcl/debug_*.tcl). SURVEY §5 maps all four onto
first-class software replacements for the TPU rebuild; this module is it:

* :class:`Profiler` — per-call timing records captured at handle-retire
  time, with per-op summary statistics (count/total/mean/p50/p95) and CSV
  export in the reference benchmark's spirit.
* :func:`annotate` and :data:`SPANS` — the program's own spans
  (``accl.*``: the driver's call, the TPU tier's rendezvous, launch and
  host staging), written as ``jax.profiler.TraceAnnotation`` events on
  the device trace's clock and kept in memory for the last profiled
  interval. Armed while any driver profiles; a disarmed span site is one
  attribute test. :func:`watch_lowerings` counts JAX lowerings into
  ``jax_lowerings_total``.
* :func:`measure_call_latency` — the ``nop`` latency probe, returning the
  same p50-style microsecond figure the reference benchmark derives.

Records are captured when the backend retires the call (the handle's done
callback), so an async chain is attributed its issue -> retire time, not
only the host's dispatch. A record is host time: on the TPU tier a
device-resident collective retires once its program is enqueued, before
the device has run it.

Two further surfaces (PR 6) make the dataplane itself observable:

* :class:`EventTrace` — a flight recorder: per-thread bounded ring buffers
  the streamed executor, egress reorder stage, combine workers, RX pool
  and fabrics emit structured stage events into
  (``recv/combine/relay/egress/cut_through/ingest/wire_send``, each with
  call_seq/lane/step/seqn/peer/nbytes/t_ns/thread). Off by default
  (``ACCL_TPU_TRACE=1`` or ``ACCL.start_trace()``); every emit site is
  behind a single ``if TRACE.enabled:`` attribute test so the disarmed
  cost is one branch. Exports Chrome/Perfetto trace-event JSON
  (:meth:`EventTrace.export_chrome`, one track per lane/worker per rank)
  — the TPU-native analog of the reference's ILA probes + waveform dumps
  (kernels/cclo/tcl/debug_*.tcl, test/simulation/cclo.wcfg). On an error
  latch or recv-deadline abort the recorder auto-dumps the last N events
  ("the waveform at the trigger", :meth:`EventTrace.trigger_dump`).
* :class:`MetricsRegistry` — a process-wide counters/gauges/histograms
  registry (labels: comm_id/peer/op/rank) absorbing the scattered stats
  surfaces (fabric stats dicts, RX-pool occupancy high-water marks,
  executor last_stats, plan-cache counters, daemon ingress rejections,
  tuner exploration picks) behind ``ACCL.metrics_snapshot()`` and a
  Prometheus-style text export. Rare events (drops, rejections) are
  counted directly; high-rate sources register weak *collectors* polled
  only at snapshot time, so the hot path pays nothing.

The process-wide singletons are ``TRACE`` and ``METRICS``.
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import json
import os
import threading
import time
import weakref

__all__ = [
    "CallRecord", "Profiler", "ProfilerSummary", "EventTrace",
    "MetricsRegistry", "SpanLog", "TRACE", "METRICS", "SPANS",
    "SPAN_CAPACITY", "annotate",
    "watch_lowerings", "measure_call_latency",
]

@dataclasses.dataclass
class CallRecord:
    """One retired call."""

    op: str                 # scenario name (allreduce, send, ...)
    count: int              # elements
    nbytes: int             # uncompressed payload bytes (count * elem size)
    comm_id: int
    t_start: float          # perf_counter seconds, host-side issue time
    duration_s: float       # issue -> retire
    error_word: int = 0
    algorithm: str = ""     # CollectiveAlgorithm name the call ran: a
    #                         concrete name where the driver/engine choice
    #                         is knowable (explicit selector, tuner pick,
    #                         or the shared-engine default), "AUTO" when a
    #                         backend resolved it internally (TPU trees),
    #                         "" when the op has no algorithm axis — what
    #                         Tuner.ingest_records keys refinement on
    #                         (concrete names only)
    # pipelined-executor counters (emu tier; 0 on backends without them):
    moves: int = 0              # move program length the call expanded to
    pipelined_moves: int = 0    # moves retired through the in-flight window
    pipeline_depth: int = 0     # peak window/segment-pipeline occupancy
    combine_overlap: int = 0    # peak CONCURRENT combines (segment-streamed
    #                             worker pool; 0 = serial/window engines,
    #                             whose combines never overlap each other)
    # compiled-plan cache counters (emu/daemon control plane):
    expand_us: float = 0.0      # host us producing the move program
    #                             (expansion + relocation on miss/bypass;
    #                             relocation only on a hit)
    plan_us: float = 0.0        # host us deriving the streamed plan
    #                             skeleton (0 on a hit — skeleton reused)
    plan_cache: str = ""        # "hit" | "miss" | "bypass" (cache
    #                             disabled) | "" (backend without a cache)
    # segment-streamed timeline derivations (ROADMAP item 5):
    lanes: int = 0              # concurrent segment lanes the streamed
    #                             plan partitioned the call into (0 on
    #                             serial/window engines and other backends)
    overlap_frac: float = 0.0   # fraction of combine time hidden behind
    #                             wire activity: measured from the flight
    #                             recorder when armed, estimated from the
    #                             pipeline counters when not; 0 for the
    #                             serial oracle (nothing ever overlaps)
    # multi-tenant service attribution (accl_tpu/service):
    tenant: str = ""            # the service tenant the call's comm
    #                             belongs to ("" on drivers without a
    #                             tenant label AND no comm grouping —
    #                             the driver defaults to "comm-<id>")
    # logical-call grouping (accl_tpu/hier): phases of one hierarchical
    # collective or redistribute program all carry the logical call's
    # tag (e.g. "hier:allreduce#3"), and the logical record itself
    # (algorithm=HIERARCHICAL / op=redistribute) carries the SAME tag —
    # group by ``parent`` and a 3-phase hierarchical allreduce reads as
    # one call in traces and metrics. "" = standalone call.
    parent: str = ""

    @property
    def duration_us(self) -> float:
        return self.duration_s * 1e6

@dataclasses.dataclass
class ProfilerSummary:
    """Aggregate statistics for one op."""

    op: str
    n: int
    total_us: float
    mean_us: float
    p50_us: float
    p95_us: float
    min_us: float
    max_us: float
    total_bytes: int

    @property
    def mean_gbps(self) -> float:
        """Mean payload goodput in GB/s (bytes moved / time in call)."""
        if self.total_us == 0:
            return 0.0
        return self.total_bytes / (self.total_us * 1e-6) / 1e9

def _percentile(sorted_vals: list[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
    return sorted_vals[idx]

class Profiler:
    """Thread-safe per-call timing recorder.

    The driver owns one and feeds it from call-handle done callbacks while
    enabled (``ACCL.start_profiling`` / ``end_profiling``). It can also be
    used standalone via :meth:`record`. A record is host time from issue
    to retire: on the TPU tier a device-resident collective retires once
    its program is enqueued, not when the device has run it.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._records: list[CallRecord] = []
        self.enabled = False

    # -- control -----------------------------------------------------------
    def start(self):
        self.enabled = True

    def stop(self):
        self.enabled = False

    def clear(self):
        with self._lock:
            self._records.clear()

    # -- capture -----------------------------------------------------------
    def record(self, rec: CallRecord):
        """Append one record — IF the profiler is armed. The flag is
        honored at record time, not attach time: a done callback attached
        while profiling was on must not keep appending after
        ``end_profiling()``/``stop()`` (async handles retire late), and a
        standalone ``record()`` obeys the same switch."""
        if not self.enabled:
            return
        with self._lock:
            self._records.append(rec)

    def attach(self, handle, op: str, count: int, nbytes: int, comm_id: int,
               t0: float | None = None, algorithm: str = "",
               tenant: str = "", parent: str = ""):
        """Register a done callback on ``handle`` that records the call's
        host-issue -> retire duration. Pass ``t0`` captured before dispatch
        so the record covers the full issue->retire window even when the
        backend retires the call before the callback is registered."""
        if t0 is None:
            t0 = time.perf_counter()

        def _on_done(error_word: int):
            # pipeline counters, when the backend published them on the
            # handle before completing it (device/emu.py _retire)
            st = getattr(handle, "pipeline_stats", None) or {}
            self.record(CallRecord(
                op=op, count=count, nbytes=nbytes, comm_id=comm_id,
                t_start=t0, duration_s=time.perf_counter() - t0,
                error_word=error_word, algorithm=algorithm,
                moves=st.get("moves", 0),
                pipelined_moves=st.get("pipelined", 0),
                pipeline_depth=st.get("max_inflight", 0),
                combine_overlap=st.get("combine_overlap", 0),
                expand_us=st.get("expand_us", 0.0),
                plan_us=st.get("plan_us", 0.0),
                plan_cache=st.get("plan_cache", ""),
                lanes=st.get("lanes", 0),
                overlap_frac=st.get("overlap_frac", 0.0),
                tenant=tenant, parent=parent))

        handle.add_done_callback(_on_done)

    # -- reporting ---------------------------------------------------------
    @property
    def records(self) -> list[CallRecord]:
        with self._lock:
            return list(self._records)

    def summary(self) -> dict[str, ProfilerSummary]:
        by_op: dict[str, list[CallRecord]] = {}
        for r in self.records:
            by_op.setdefault(r.op, []).append(r)
        out = {}
        for op, recs in sorted(by_op.items()):
            durs = sorted(r.duration_us for r in recs)
            out[op] = ProfilerSummary(
                op=op, n=len(recs), total_us=sum(durs),
                mean_us=sum(durs) / len(durs),
                p50_us=_percentile(durs, 0.50),
                p95_us=_percentile(durs, 0.95),
                min_us=durs[0], max_us=durs[-1],
                total_bytes=sum(r.nbytes for r in recs))
        return out

    def table(self) -> str:
        rows = [f"{'op':<16}{'n':>6}{'mean_us':>12}{'p50_us':>12}"
                f"{'p95_us':>12}{'GB/s':>10}"]
        for s in self.summary().values():
            rows.append(f"{s.op:<16}{s.n:>6}{s.mean_us:>12.2f}"
                        f"{s.p50_us:>12.2f}{s.p95_us:>12.2f}"
                        f"{s.mean_gbps:>10.3f}")
        return "\n".join(rows)

    def to_csv(self, path: str):
        """Raw record dump, one row per retired call — the shape the
        reference benchmark writes (bench_*.csv, test/host/test.py:949)."""
        with open(path, "w") as f:
            f.write("op,count,nbytes,comm_id,t_start,duration_us,error,"
                    "algorithm,moves,pipelined_moves,pipeline_depth,"
                    "combine_overlap,expand_us,plan_us,plan_cache,"
                    "lanes,overlap_frac,tenant,parent\n")
            for r in self.records:
                f.write(f"{r.op},{r.count},{r.nbytes},{r.comm_id},"
                        f"{r.t_start:.9f},{r.duration_us:.3f},"
                        f"{r.error_word},{r.algorithm},{r.moves},"
                        f"{r.pipelined_moves},{r.pipeline_depth},"
                        f"{r.combine_overlap},{r.expand_us:.1f},"
                        f"{r.plan_us:.1f},{r.plan_cache},"
                        f"{r.lanes},{r.overlap_frac:.4f},{r.tenant},"
                        f"{r.parent}\n")

    @staticmethod
    def read_csv(path: str) -> list[CallRecord]:
        """Parse a :meth:`to_csv` dump back into records (export/import
        round trip — e.g. to feed an offline run's history into a
        ``Tuner`` via ``ingest_records``). Dumps from before the
        ``algorithm`` / pipeline-counter columns read back with those
        fields empty/zero."""
        import csv as _csv

        out = []
        with open(path, newline="") as f:
            for row in _csv.DictReader(f):
                out.append(CallRecord(
                    op=row["op"], count=int(row["count"]),
                    nbytes=int(row["nbytes"]),
                    comm_id=int(row["comm_id"]),
                    t_start=float(row["t_start"]),
                    duration_s=float(row["duration_us"]) * 1e-6,
                    error_word=int(row["error"]),
                    algorithm=row.get("algorithm") or "",
                    moves=int(row.get("moves") or 0),
                    pipelined_moves=int(row.get("pipelined_moves") or 0),
                    pipeline_depth=int(row.get("pipeline_depth") or 0),
                    combine_overlap=int(row.get("combine_overlap") or 0),
                    expand_us=float(row.get("expand_us") or 0.0),
                    plan_us=float(row.get("plan_us") or 0.0),
                    plan_cache=row.get("plan_cache") or "",
                    lanes=int(row.get("lanes") or 0),
                    overlap_frac=float(row.get("overlap_frac") or 0.0),
                    tenant=row.get("tenant") or "",
                    parent=row.get("parent") or ""))
        return out

# -- flight recorder --------------------------------------------------------
#
# Event tuple layout (kept a plain tuple — an emit is one monotonic clock
# read plus a deque append, no object construction beyond the tuple):
#   (t_ns, dur_ns, stage, rank, call_seq, lane, step, seqn, peer, nbytes,
#    thread_name, tenant)
# ``tenant`` ("" when unattributed) was APPENDED so every positional
# consumer of the earlier 11-field layout (overlap_frac's raw-ring scan)
# reads unchanged indices.
_EV_FIELDS = ("t_ns", "dur_ns", "stage", "rank", "call_seq", "lane",
              "step", "seqn", "peer", "nbytes", "thread", "tenant")

# wire-activity stages (what combine time can hide behind) vs compute.
# "wire_send" is NOT here: fabric send events are instants (dur_ns=0, no
# call_seq), so they can never contribute an interval — the egress/recv
# stages bracketing them carry the wire time instead. "ingest" (also an
# instant, no call_seq — the pool cannot know the consuming call) is
# matched to the call's recv events by (rank, peer, seqn) in
# :meth:`EventTrace.overlap_frac`: the msg-gated scheduler never parks a
# recv, so the frame's flight + pool residency (ingest → consumption) IS
# the wire interval, not the near-instant fetch.
_WIRE_STAGES = frozenset({"recv", "relay", "egress", "cut_through"})


class EventTrace:
    """Bounded per-thread-ring flight recorder with Chrome-trace export.

    Arming: ``ACCL_TPU_TRACE=1`` in the environment arms the process-wide
    instance (``TRACE``) at import; :meth:`start`/:meth:`stop` toggle at
    runtime (``ACCL.start_trace()``). Every producer site guards with
    ``if TRACE.enabled:`` — ONE attribute load and branch when disarmed,
    which is what keeps the recorder compile-in-but-free (the tier-1
    overhead test times exactly this guard).

    Buffering is per THREAD: each emitting thread appends to its own
    ``deque(maxlen=capacity)`` — no lock on the hot path; the deque drops
    the oldest event when full (flight-recorder semantics: the ring always
    holds the most recent window, i.e. the waveform AT the trigger).
    Thread buffers register once under a lock and are kept by strong
    reference so a finished worker's tail is still exportable.
    """

    def __init__(self, capacity: int | None = None):
        if capacity is None:
            capacity = int(os.environ.get("ACCL_TPU_TRACE_EVENTS", 65536))
        self.capacity = max(256, int(capacity))
        self.enabled = os.environ.get("ACCL_TPU_TRACE", "").lower() in (
            "1", "true", "on", "yes")
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._buffers: list[tuple[threading.Thread, collections.deque]] = []
        self._call_seq = itertools.count(1)
        # auto-dump ("waveform at the trigger") configuration: where the
        # Chrome JSON lands and how many dumps one arming may write (an
        # abort storm must not fill the disk with identical rings)
        self.dump_dir = os.environ.get("ACCL_TPU_TRACE_DUMP_DIR") or ""
        self.max_dumps = int(os.environ.get("ACCL_TPU_TRACE_MAX_DUMPS", 4))
        self._dumps = 0
        self.dump_paths: list[str] = []

    # -- control -----------------------------------------------------------
    def start(self):
        # fresh dump budget per arming: trigger_dump is "bounded by
        # max_dumps per arming", so a session re-armed after a dump storm
        # must get its waveforms-at-the-trigger again
        self._dumps = 0
        self.enabled = True

    def stop(self):
        self.enabled = False

    def clear(self):
        with self._lock:
            # rings of dead threads are unreachable for new events and
            # their history is being discarded anyway — drop the entries
            # so a long armed session of short-lived worlds (each world
            # spawns fresh worker/egress threads) doesn't grow the table
            # without bound
            self._buffers = [(t, b) for t, b in self._buffers
                             if t.is_alive()]
            for _, buf in self._buffers:
                buf.clear()
            self._dumps = 0
            self.dump_paths.clear()

    def next_call_seq(self) -> int:
        """Process-unique call sequence number tying one call's events
        together across threads/ranks. ``itertools.count`` because a bare
        ``+=`` is three bytecodes — concurrent rank threads entering
        their executors could both read N and collide, merging two calls'
        events under one seq (ordering is by timestamp anyway)."""
        return next(self._call_seq)

    # -- capture -----------------------------------------------------------
    def _buffer(self) -> collections.deque:
        buf = getattr(self._tls, "buf", None)
        if buf is None:
            buf = collections.deque(maxlen=self.capacity)
            self._tls.buf = buf
            with self._lock:
                if len(self._buffers) >= 256:
                    # thread-churn bound (registration is rare, so the
                    # sweep amortizes to nothing): past any plausible
                    # live-world thread count, evict dead threads' rings.
                    # Their events leave future exports — the recorder
                    # keeps recent history, not all history.
                    self._buffers = [(t, b) for t, b in self._buffers
                                     if t.is_alive()]
                self._buffers.append((threading.current_thread(), buf))
        return buf

    def emit(self, stage: str, *, rank: int = -1, call_seq: int = 0,
             lane: int = -1, step: int = -1, seqn: int = -1,
             peer: int = -1, nbytes: int = 0, t_ns: int | None = None,
             dur_ns: int = 0, tenant: str = ""):
        """Record one structured event. ``t_ns`` is the event START
        (monotonic ns; now when omitted), ``dur_ns`` its duration (0 for
        instantaneous events); ``tenant`` attributes the event to a
        service tenant (multi-tenant Perfetto tracks). Callers on the hot
        path must pre-check ``enabled`` — this method rechecks only to
        tolerate a disarm race.
        """
        if not self.enabled:
            return
        if t_ns is None:
            t_ns = time.monotonic_ns()
        self._buffer().append(
            (t_ns, dur_ns, stage, rank, call_seq, lane, step, seqn, peer,
             nbytes, threading.current_thread().name, tenant))

    # -- reporting ----------------------------------------------------------
    def events(self) -> list[dict]:
        """Merged time-sorted snapshot of every thread's ring, as dicts."""
        with self._lock:
            rings = [buf for _, buf in self._buffers]
        raw = [ev for buf in rings for ev in list(buf)]
        raw.sort(key=lambda e: e[0])
        return [dict(zip(_EV_FIELDS, ev)) for ev in raw]

    def export_chrome(self, path: str, events: list[dict] | None = None
                      ) -> int:
        """Write Chrome/Perfetto trace-event JSON: one *process* per rank,
        one *track* (tid) per lane — unlaned events track under their
        emitting thread — so a streamed collective renders as a visual
        pipeline (chrome://tracing or ui.perfetto.dev). Returns the number
        of events written."""
        evs = self.events() if events is None else events
        t0 = min((e["t_ns"] for e in evs), default=0)
        # (rank, track label) -> tid, assigned in first-seen order
        tids: dict[tuple[int, str], int] = {}
        out: list[dict] = []
        for e in evs:
            pid = e["rank"] if e["rank"] >= 0 else 0
            label = (f"lane {e['lane']}" if e["lane"] >= 0
                     else str(e["thread"]))
            tenant = e.get("tenant", "")
            if tenant:
                # tenant-prefixed tracks: two tenants' same-numbered
                # lanes render as separate interleaved tracks instead of
                # merging into one indistinguishable timeline
                label = f"{tenant} {label}"
            key = (pid, label)
            tid = tids.get(key)
            if tid is None:
                tid = tids[key] = len(tids) + 1
                out.append({"ph": "M", "name": "thread_name", "pid": pid,
                            "tid": tid, "args": {"name": label}})
            args = {k: e[k] for k in ("call_seq", "step", "seqn", "peer",
                                      "nbytes") if e[k] not in (-1,)}
            args["thread"] = e["thread"]
            if tenant:
                args["tenant"] = tenant
            out.append({"ph": "X", "name": e["stage"], "cat": "accl_tpu",
                        "pid": pid, "tid": tid,
                        "ts": (e["t_ns"] - t0) / 1e3,
                        "dur": e["dur_ns"] / 1e3, "args": args})
        for pid in sorted({e["rank"] if e["rank"] >= 0 else 0
                           for e in evs}):
            out.append({"ph": "M", "name": "process_name", "pid": pid,
                        "tid": 0, "args": {"name": f"rank {pid}"}})
        with open(path, "w") as f:
            json.dump({"traceEvents": out, "displayTimeUnit": "ms"}, f)
        return len(evs)

    def overlap_frac(self, call_seq: int) -> float | None:
        """Measured overlap for one call: the fraction of its combine time
        that lies under the union of its wire-activity intervals
        (recv/relay/egress/cut-through) — "combine hidden
        behind the wire", ROADMAP item 5. None when the ring holds no
        combine events for the call (evicted, or armed mid-call).

        Wire intervals are widened to the frame's true flight span,
        matched by (receiver, sender, wire seqn) key: a recv reaches
        BACK to its frame's ``wire_send``/pool-``ingest`` instant (the
        msg-gated scheduler dispatches a recv only once its frame is
        already pooled, so the fetch itself is near-instant — the
        flight + pool residency is what a concurrent combine hides), and
        an egress reaches FORWARD to the peer's ``ingest`` (after the
        local send returns, the frame is still in flight until the
        receiver pools it). Those instants carry no call_seq (fabric and
        pool cannot know the consuming call), hence the key match; seqns
        are per-(src, comm, dst) monotonic, so the nearest instant in
        the right direction belongs to the frame in hand.

        Runs once per RETIRED call on armed runs, so it scans raw tuples
        filtered by call_seq — never :meth:`events`, whose whole-ring
        dict conversion and time sort would make every retire
        O(capacity)."""
        combine: list[tuple[int, int]] = []
        wire: list[tuple[int, int]] = []
        recvs: list[tuple[tuple[int, int, int], int, int]] = []
        egress: list[tuple[tuple[int, int, int], int, int]] = []
        ingests: dict[tuple[int, int, int], list[int]] = {}
        sends: dict[tuple[int, int, int], list[int]] = {}
        # the lock guards only the _buffers table; each ring copy is a
        # GIL-atomic list(deque), so copying OUTSIDE the lock keeps
        # concurrent retirements/exports from serializing on each other
        with self._lock:
            rings = [buf for _, buf in self._buffers]
        bufs = [list(buf) for buf in rings]
        for buf in bufs:
            for ev in buf:  # (t_ns, dur_ns, stage, rank, call_seq, lane,
                #              step, seqn, peer, nbytes, thread)
                if ev[2] == "ingest":
                    # keyed (receiver, sender, seqn) — mirrored by the
                    # consuming recv event as (rank, peer, seqn)
                    ingests.setdefault((ev[3], ev[8], ev[7]),
                                       []).append(ev[0])
                    continue
                if ev[2] == "wire_send":
                    # sender-side instant: keyed (receiver, sender, seqn)
                    # to line up with the consuming recv's (rank, peer,
                    # seqn) — this marks the START of the frame's flight
                    sends.setdefault((ev[8], ev[3], ev[7]),
                                     []).append(ev[0])
                    continue
                if ev[4] != call_seq or ev[1] <= 0:
                    continue
                span = (ev[0], ev[0] + ev[1])
                if ev[2] == "combine":
                    combine.append(span)
                elif ev[2] in _WIRE_STAGES:
                    if ev[2] == "recv" and ev[7] >= 0:
                        recvs.append(((ev[3], ev[8], ev[7]),
                                      span[0], span[1]))
                        continue
                    if ev[2] == "egress" and ev[7] >= 0:
                        egress.append(((ev[8], ev[3], ev[7]),
                                       span[0], span[1]))
                        continue
                    wire.append(span)
        if not combine:
            return None
        for key, s, t in recvs:
            # per stage, the LATEST instant at or before consumption end
            # belongs to this frame (seqns are in-order per key; earlier
            # entries are other comms' colliding triples); between the
            # stages take the EARLIER — wire_send marks flight start,
            # ingest only pool arrival
            for d in (sends, ingests):
                ts = [it for it in d.get(key, ()) if it <= t]
                if ts:
                    s = min(s, max(ts))
            wire.append((s, t))
        for key, s, t in egress:
            # the EARLIEST ingest at or after the send start is this
            # frame's delivery; until then it is in flight on the fabric
            ts = [it for it in ingests.get(key, ()) if it >= s]
            if ts:
                t = max(t, min(ts))
            wire.append((s, t))
        # merge wire intervals into a disjoint sorted union
        wire.sort()
        merged: list[list[int]] = []
        for s, t in wire:
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], t)
            else:
                merged.append([s, t])
        total = hidden = 0
        for s, t in combine:
            total += t - s
            for ws, wt in merged:
                if wt <= s:
                    continue
                if ws >= t:
                    break
                hidden += min(t, wt) - max(s, ws)
        return hidden / total if total else 0.0

    # -- auto-dump ("the waveform at the trigger") ---------------------------
    def trigger_dump(self, reason: str, rank: int = -1) -> str | None:
        """Dump the ring to a Chrome-trace file on a failure trigger
        (error latch, recv-deadline abort). Bounded by ``max_dumps`` per
        arming; best-effort — a full disk must never break the abort path
        itself. Returns the path written, None when skipped/failed."""
        if not self.enabled:
            return None
        with self._lock:
            if self._dumps >= self.max_dumps:
                return None
            self._dumps += 1
            n = self._dumps
        import tempfile
        safe = "".join(c if c.isalnum() or c in "-_" else "_"
                       for c in reason)[:48]
        path = os.path.join(
            self.dump_dir or tempfile.gettempdir(),
            f"accl_tpu_trace_{os.getpid()}_{n}_{safe}.json")
        try:
            nev = self.export_chrome(path)
        except OSError:
            return None
        self.dump_paths.append(path)
        from .log import get_logger
        get_logger("tracing").warning(
            "rank %s: flight recorder dumped %d events to %s (%s)",
            rank if rank >= 0 else "-", nev, path, reason)
        return path


# -- metrics registry --------------------------------------------------------

def health_rows(owner, labels: dict):
    """Collector rows for one rank's execution backend — rx pool,
    move executor, plan cache — reported off whichever of those surfaces
    ``owner`` actually has. ONE mapping shared by the device
    (``device/base._device_metrics_rows``) and daemon
    (``emulator/daemon._daemon_metrics_rows``) collectors, so the
    ``tier=device`` and ``tier=daemon`` series can never drift in which
    gauges they report or what they are named."""
    pool = getattr(owner, "pool", None)
    if pool is not None:
        yield ("gauge", "rx_pool_occupancy", labels, pool.occupancy())
        yield ("gauge", "rx_pool_occupancy_hwm", labels, pool.hwm)
        yield ("gauge", "rx_pool_size", labels, len(pool.bufs))
    ex = getattr(owner, "executor", None)
    if ex is not None:
        for k, v in ex.last_stats.items():
            yield ("gauge", f"executor_last_{k}", labels, v)
    cache = getattr(owner, "plan_cache", None)
    if cache is not None and hasattr(cache, "metrics_rows"):
        yield from cache.metrics_rows(labels)


class MetricsRegistry:
    """Process-wide counters/gauges/histograms with Prometheus-style
    labels, plus weakly-held *collectors* polled at snapshot time.

    Two write disciplines, by event rate:

    * rare events (fabric drops/corruption, ingress rejections, tuner
      exploration picks, per-call accounting) write directly via
      :meth:`inc`/:meth:`observe` — one lock round-trip each;
    * high-rate sources (fabric stats dicts, RX-pool occupancy, executor
      last_stats, plan caches) keep their existing cheap counters and
      register a collector closure that converts them to labeled rows
      ONLY when a snapshot is taken. Collectors hold their owner weakly:
      tests spin thousands of worlds per session, and a dead world's
      fabric must neither leak nor keep reporting.
    """

    _HIST_BUCKETS = tuple(4.0 ** k for k in range(0, 10))  # 1..4^9, +Inf

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[tuple, float] = {}
        self._gauges: dict[tuple, float] = {}
        self._hists: dict[tuple, list] = {}  # key -> [count, sum, [bucket n]]
        self._collectors: list[tuple[weakref.ref, object]] = []

    @staticmethod
    def _key(name: str, labels: dict) -> tuple:
        return (name, tuple(sorted((k, str(v)) for k, v in labels.items())))

    # -- direct writes -----------------------------------------------------
    def inc(self, name: str, value: float = 1, **labels):
        key = self._key(name, labels)
        with self._lock:
            self._counters[key] = self._counters.get(key, 0) + value

    def counter(self, name: str, **labels) -> float:
        """A directly-written counter's value (0 before its first write)."""
        return self._counters.get(self._key(name, labels), 0)

    def set_gauge(self, name: str, value: float, **labels):
        with self._lock:
            self._gauges[self._key(name, labels)] = value

    def observe(self, name: str, value: float, **labels):
        """Histogram sample (fixed power-of-4 buckets in the observed
        unit)."""
        key = self._key(name, labels)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = [0, 0.0,
                                        [0] * (len(self._HIST_BUCKETS) + 1)]
            h[0] += 1
            h[1] += value
            for i, edge in enumerate(self._HIST_BUCKETS):
                if value <= edge:
                    h[2][i] += 1
                    break
            else:
                h[2][-1] += 1

    # -- collectors --------------------------------------------------------
    def register_collector(self, owner, fn):
        """``fn(owner) -> iterable of (kind, name, labels_dict, value)``
        with kind "counter" | "gauge" | "histogram" (histogram value:
        ``[count, sum, bucket-count list]`` over ``_HIST_BUCKETS`` edges
        — the service layer folds its locally-kept queue-wait histograms
        through this). ``owner`` is held weakly — the collector vanishes
        with it."""
        with self._lock:
            self._collectors = [(r, f) for r, f in self._collectors
                                if r() is not None]
            self._collectors.append((weakref.ref(owner), fn))

    def _collect(self) -> list[tuple[str, str, dict, float]]:
        with self._lock:
            refs = list(self._collectors)
        rows = []
        for ref, fn in refs:
            owner = ref()
            if owner is None:
                continue
            try:
                rows.extend(fn(owner))
            except Exception:  # noqa: BLE001 — a dying world's collector
                # must not take the whole snapshot down with it
                continue
        return rows

    # -- reporting ---------------------------------------------------------
    @staticmethod
    def _label_str(labels: tuple) -> str:
        return ",".join(f"{k}={v}" for k, v in labels)

    def snapshot(self) -> dict:
        """One nested dict: ``{"counters": {name: {"k=v,...": value}},
        "gauges": {...}, "histograms": {name: {labels: {count,sum,
        buckets}}}}`` — direct writes merged with every live collector's
        rows."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {k: [h[0], h[1], list(h[2])]
                     for k, h in self._hists.items()}
        for kind, name, labels, value in self._collect():
            key = self._key(name, labels)
            if kind == "counter":
                counters[key] = counters.get(key, 0) + value
            elif kind == "histogram":
                # value: [count, sum, bucket list] over _HIST_BUCKETS
                h = hists.get(key)
                if h is None:
                    hists[key] = [value[0], value[1], list(value[2])]
                else:
                    h[0] += value[0]
                    h[1] += value[1]
                    h[2] = [a + b for a, b in zip(h[2], value[2])]
            else:
                gauges[key] = value
        out = {"counters": {}, "gauges": {}, "histograms": {}}
        for key, v in counters.items():
            out["counters"].setdefault(key[0], {})[
                self._label_str(key[1])] = v
        for key, v in gauges.items():
            out["gauges"].setdefault(key[0], {})[
                self._label_str(key[1])] = v
        for key, (n, s, buckets) in hists.items():
            edges = [*(str(e) for e in self._HIST_BUCKETS), "+Inf"]
            out["histograms"].setdefault(key[0], {})[
                self._label_str(key[1])] = {
                    "count": n, "sum": s,
                    "buckets": dict(zip(edges, buckets))}
        return out

    def to_prometheus(self) -> str:
        """Prometheus text exposition of :meth:`snapshot` (counter/gauge
        families plus cumulative histogram buckets)."""
        snap = self.snapshot()
        lines = []

        def fmt(name, labels, value):
            lab = ("{" + ",".join(
                f'{k}="{v}"' for k, v in
                (p.split("=", 1) for p in labels.split(","))) + "}"
                if labels else "")
            lines.append(f"{name}{lab} {value}")

        for kind in ("counters", "gauges"):
            ptype = "counter" if kind == "counters" else "gauge"
            for name in sorted(snap[kind]):
                lines.append(f"# TYPE {name} {ptype}")
                for labels in sorted(snap[kind][name]):
                    fmt(name, labels, snap[kind][name][labels])
        for name in sorted(snap["histograms"]):
            lines.append(f"# TYPE {name} histogram")
            for labels in sorted(snap["histograms"][name]):
                h = snap["histograms"][name][labels]
                cum = 0
                for edge, n in h["buckets"].items():
                    cum += n
                    le = f"le={edge}"
                    lab = f"{labels},{le}" if labels else le
                    fmt(f"{name}_bucket", lab, cum)
                fmt(f"{name}_sum", labels, h["sum"])
                fmt(f"{name}_count", labels, h["count"])
        return "\n".join(lines) + "\n"

    def reset(self):
        """Drop every directly-written series (collectors stay registered
        — their sources own their own lifecycle). Test isolation helper."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()


# -- program spans on the device trace's clock --------------------------------

# Entries SPANS keeps for one armed interval. A traced 20 s window of the
# busiest chip benchmark cell records ~0.2M; past this many a span is
# counted in SPANS.dropped and not kept.
SPAN_CAPACITY = 1 << 19


class _Span:
    """One armed span: a ``TraceAnnotation`` (the profiler's trace, on the
    device's clock) and, at exit, an entry in :data:`SPANS`. The entry's
    start is taken before the annotation is built and its end after the
    annotation has closed, so a span's own cost lies inside it and not in
    its parent's self time."""

    __slots__ = ("_tm", "_t0", "name", "args")

    def __init__(self, name: str, args: dict):
        self._t0 = time.perf_counter_ns()
        self.name, self.args = name, args
        self._tm = SPANS.annotation(name, **args)

    def set(self, **args):
        """Metadata known only inside the span (a rendezvous slot)."""
        self.args.update(args)
        self._tm.set_metadata(**args)

    def __enter__(self):
        self._tm.__enter__()
        return self

    def __exit__(self, *exc):
        self._tm.__exit__(*exc)
        SPANS.add(self.name, self._t0, time.perf_counter_ns(), self.args)


class _NoSpan:
    """What :func:`annotate` returns while spans are off."""

    def set(self, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


_NO_SPAN = _NoSpan()


class SpanLog:
    """The program's spans (``accl.*``), armed while any driver profiles
    (``ACCL.start_profiling`` .. ``end_profiling``, counted across
    drivers: :meth:`arm` / :meth:`disarm`).

    A span site tests ``SPANS.enabled`` and only then calls
    :func:`annotate`, so a disarmed site builds nothing. An armed span is
    a ``jax.profiler.TraceAnnotation`` — its args are the event's
    metadata, on the clock of the device ops when a profiler trace is
    being taken — and an entry ``(name, thread, t0_ns, t1_ns, args)``
    kept in memory until the next arming, so a reader in this process
    gets the last profiled interval without a trace file. The first
    :data:`SPAN_CAPACITY` entries of an interval are kept; ``dropped``
    counts those past it. Spans of one call share ``call``; the spans of
    one collective on every member's thread share ``group``.
    ``lowerings`` is the growth of ``jax_lowerings_total`` over the last
    armed interval (None while armed, or before the first)."""

    def __init__(self):
        self.enabled = False
        self.lowerings: int | None = None
        self.dropped = 0
        self.annotation = None      # jax.profiler.TraceAnnotation, once armed
        self._holders = 0
        self._lowered0 = 0.0
        self._lock = threading.Lock()
        self._spans: list = []
        self._calls = itertools.count(1)

    def arm(self):
        with self._lock:
            self._holders += 1
            if self._holders > 1:
                return
            if self.annotation is None:
                from jax.profiler import TraceAnnotation
                self.annotation = TraceAnnotation
            self._spans = []
            self.dropped = 0
            self.lowerings = None
            self._lowered0 = METRICS.counter("jax_lowerings_total")
            self.enabled = True

    def disarm(self):
        with self._lock:
            if self._holders == 0:
                return
            self._holders -= 1
            if self._holders == 0:
                self.enabled = False
                self.lowerings = int(METRICS.counter("jax_lowerings_total")
                                     - self._lowered0)

    def next_call(self) -> int:
        """A per-process call id (``itertools.count``: concurrent rank
        threads never share one)."""
        return next(self._calls)

    def add(self, name: str, t0_ns: int, t1_ns: int, args: dict):
        if len(self._spans) < SPAN_CAPACITY:
            self._spans.append((name, threading.get_ident(), t0_ns, t1_ns,
                                args))
        else:
            self.dropped += 1

    def spans(self) -> list[tuple]:
        """``(name, thread, t0_ns, t1_ns, args)`` of the last armed
        interval, in order of their end (``time.perf_counter_ns``)."""
        return list(self._spans)


# Process-wide singletons: the whole point is ONE health surface across
# every world/daemon living in this process.
TRACE = EventTrace()
METRICS = MetricsRegistry()
SPANS = SpanLog()


def annotate(name: str, **args):
    """A span named ``name`` whose ``args`` become its trace metadata (on
    a CPU trace they read back as ``event.stats``); a no-op context while
    :data:`SPANS` is disarmed. The one way the program emits a span."""
    if not SPANS.enabled:
        return _NO_SPAN
    return _Span(name, args)


_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_lowerings_lock = threading.Lock()
_lowerings_watched = False


def _on_jax_duration(event: str, duration_secs: float, **kwargs):
    if event == _LOWERING_EVENT:
        METRICS.inc("jax_lowerings_total")


def watch_lowerings():
    """Count every JAX lowering from now on — a jit cache miss, whether or
    not the persistent cache then supplies the executable — into
    ``jax_lowerings_total``. Idempotent; the TPU tier calls it when a
    world is built, so a warm window reads 0."""
    global _lowerings_watched
    with _lowerings_lock:
        if _lowerings_watched:
            return
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(_on_jax_duration)
        _lowerings_watched = True


def measure_call_latency(accl, n: int = 100) -> dict[str, float]:
    """Round-trip latency of the full call path via ``nop``.

    Parity: the reference warms up and times nop calls to isolate call
    overhead from data movement (test/host/test.py:934-936).
    """
    for _ in range(min(n, 10)):  # warmup
        accl.nop()
    samples = []
    for _ in range(n):
        t0 = time.perf_counter()
        accl.nop()
        samples.append((time.perf_counter() - t0) * 1e6)
    samples.sort()
    return {
        "n": float(n),
        "p50_us": _percentile(samples, 0.50),
        "p95_us": _percentile(samples, 0.95),
        "mean_us": sum(samples) / len(samples),
        "min_us": samples[0],
    }
