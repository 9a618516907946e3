"""The ACCL driver: the user-facing host API.

Method-for-method capability parity with the reference's canonical PYNQ
driver class (driver/pynq/accl.py:293-985): buffer management, communicator
and arithmetic configuration, the full primitive/collective surface
(``nop/send/recv/copy/combine/bcast/scatter/gather/reduce/allgather/
allreduce/reduce_scatter``), sync/async call forms with ``waitfor=``
chaining, error decode, and introspection dumps. Extensions the TPU build
adds as first-class: ``barrier``, ``alltoall``, algorithm selectors, and
mesh-backed execution (device/tpu.py).

Buffers are uncompressed/compressed pairs exactly like the reference's
``prepare_call`` dtype resolution: a call may mix at most two dtypes, the
narrower of which is the "compressed" form, with per-operand compression
flags computed automatically (accl.py:528-592).
"""

from __future__ import annotations

import contextlib
import itertools
from typing import Any, Sequence

import numpy as np

from .arith import DEFAULT_ARITH_CONFIGS, resolve_arith_config
from .buffer import ACCLBuffer
from .call import CallDescriptor, CallHandle, CompletedHandle
from .communicator import Communicator
from .constants import (ACCLError, CCLOp, CfgFunc, CollectiveAlgorithm,
                        Compression, DEFAULT_ALGORITHMS,
                        DEFAULT_MAX_SEGMENT_SIZE, ErrorCode,
                        HIERARCHICAL_OPS, ReduceFunc, StreamFlags, TAG_ANY,
                        VALID_ALGORITHMS)
from .device.base import Device
from .log import get_logger
from .retry import RetryPolicy, resolve_policy
from .tracing import METRICS, Profiler, SPANS, TRACE, annotate

log = get_logger(__name__)


def _phases_strip_flat(compress_phases: str | None) -> bool:
    """Validate a per-phase compression selector and answer whether a
    FLAT (non-hierarchical) execution should drop the wire compression:
    "inter" compresses only inter-host hierarchy phases, and a flat call
    has none — EQuARX semantics, where intra-host traffic always stays
    full precision."""
    if compress_phases in (None, "all"):
        return False
    if compress_phases == "inter":
        return True
    raise ValueError(
        f"compress_phases must be None, 'all' or 'inter', got "
        f"{compress_phases!r}")


class ACCL:
    """One rank's handle to the collective engine.

    Args:
        device: the execution backend (EmuDevice / SimDevice / TpuDevice).
        comm: the world communicator for this rank.
        timeout: receive timeout in seconds (set_timeout parity).
        max_segment_size: wire segmentation granularity. When None, the
            attached tuner recommends one against the backend's
            ``preferred_segment_size()`` (no tuner: the preferred size).
        tuner: optional :class:`~accl_tpu.tuner.Tuner` resolving AUTO
            algorithm selectors by size/topology and learning from
            retire-time measurements. Multi-rank worlds must share ONE
            tuner instance across their ranks (all member ranks of a
            collective must agree on the algorithm).
        tenant: optional multi-tenant service label (accl_tpu/service):
            every communicator this driver registers is grouped under it
            for admission scheduling, resource quotas and per-tenant
            metrics/trace attribution. Default: each communicator is its
            own tenant.
    """

    def __init__(self, device: Device, comm: Communicator,
                 timeout: float = 30.0,
                 max_segment_size: int | None = None,
                 arith_registry=None, tuner=None,
                 tenant: str | None = None,
                 retry_policy: "RetryPolicy | None" = None,
                 verify_integrity: bool = False):
        self.device = device
        # Tier-2 integrity (PR 13): verify replicated-result collectives
        # (allreduce / allgather / bcast) by fingerprinting the result
        # buffer (crc32 — cheap, and exact because the engines hold
        # results bit-identical across ranks) and cross-checking the
        # fingerprints in a small follow-up allgather. Catches what
        # retransmission cannot: LOCAL combine/scratch/memory corruption
        # that lands a wrong result with a clean wire. A mismatch raises
        # typed DATA_INTEGRITY_ERROR naming the disagreeing rank(s) —
        # never blind-retried (retry.py). Must be UNIFORM across the
        # ranks of a communicator (the exchange is itself a collective),
        # like retry policies. Sync calls only; per-call
        # ``verify_integrity=`` overrides either way.
        self.verify_integrity = bool(verify_integrity)
        # driver-wide default retry policy (accl_tpu/retry.py): applied
        # to every data call unless a per-call retries=/retry_policy=
        # overrides it. Must be UNIFORM across the ranks of a
        # communicator, like the collectives themselves.
        self.retry_policy = retry_policy
        self._preflight_warned: set = set()
        if tenant is not None:
            from .service import validate_tenant
            validate_tenant(tenant)  # label is spliced into CSV/metrics/
            # trace encodings — reject unsafe charsets at the API edge
        self.tenant = tenant
        self._arith_memo: dict[frozenset, object] = {}
        self.arith_registry = (arith_registry if arith_registry is not None
                               else dict(DEFAULT_ARITH_CONFIGS))
        self.communicators: list[Communicator] = []
        # per-global-rank address book: the most recently registered
        # Rank record for each global rank this driver has ever seen
        # (grow_communicator's member-record resolution source — see
        # _register_comm for why the comm registry's order is not
        # recency)
        self._rank_book: dict[int, "Rank"] = {}
        self._barrier_buf: ACCLBuffer | None = None
        self._scratch_bufs: dict[tuple[int, str], ACCLBuffer] = {}
        self.profiler = Profiler()
        self._holds_spans = False   # this driver keeps SPANS armed
        self.tuner = tuner
        # two-tier hierarchy (accl_tpu/hier): configured explicitly via
        # configure_hierarchy() or auto-derived once from a tuner's
        # MeshTopology on the first AUTO-resolved collective
        self._hier = None
        self._hier_autoprobe = True
        # logical-call attribution: phases of a hierarchical/redistribute
        # program record this tag as CallRecord.parent (one driver is
        # used from one thread at a time — the established driver
        # threading contract)
        self._parent_tag = ""
        # redistribution engine state: memoized plans (pure geometry),
        # cached member-subset sub-communicators, and recycled async
        # staging buffers (popped at issue by the driver thread,
        # appended back by the completion callback — GIL-atomic ops)
        self._redist_plans: dict = {}
        self._redist_comms: dict = {}
        self._redist_stage_pool: dict = {}
        self._redist_seq = itertools.count(1)
        # one-sided RMA windows (accl_tpu/rma): ids handed out from a
        # per-driver counter, so symmetric registration order yields
        # agreeing ids across ranks without a handshake — the same
        # determinism contract split_communicator uses for comm ids
        self._next_window = itertools.count(1)
        self._windows: dict[int, ACCLBuffer] = {}
        # async calls this driver has issued that have not retired yet —
        # tuner-training measurements only happen on a quiet device
        # (an unrelated in-flight call would add its queue wait to the
        # measured window)
        import threading as _threading
        self._async_mu = _threading.Lock()
        self._async_inflight = 0
        # per-communicator call/byte accounting (QoS attribution
        # foundation, ROADMAP item 3). Kept as plain driver-local dicts —
        # the per-call hot path is GIL-cheap dict arithmetic, no
        # process-wide lock — and folded into the registry by a WEAK
        # collector only when someone snapshots. (op, comm_id) -> n.
        self._call_counts: dict[tuple, int] = {}
        self._byte_counts: dict[tuple, int] = {}
        METRICS.register_collector(self, ACCL._metrics_rows)
        if tuner is not None:
            if tuner.topology is None:
                tuner.topology = device.topology()
            # engine-level AUTO resolution for descriptors that reach the
            # move engine still unresolved (moveengine.expand_call)
            device.tuner = tuner
            # tuner re-resolution (refresh/pin — the points where
            # epsilon-greedy or EWMA switching can flip a decision) must
            # invalidate the device's compiled-plan cache: a switched
            # algorithm lands on a new key, and stale entries for the
            # old choice are dropped rather than accumulated
            cache = getattr(device, "plan_cache", None)
            if cache is not None:
                tuner.register_plan_cache(cache)
            # fleet-shared tuning table (tuner/cache.py env override):
            # pins load best-effort — a missing/stale cache is not an
            # error — and once per tuner, not once per rank sharing it
            from .tuner import cache as _tcache
            if (_tcache.default_cache_path()
                    and not getattr(tuner, "_env_cache_loaded", False)):
                tuner._env_cache_loaded = True
                try:
                    _tcache.load_into(tuner)
                except (OSError, ValueError):
                    pass
        device.configure_communicator(comm, tenant=tenant)
        self._register_comm(comm)
        # bring-up sequence through the call path, mirroring the reference
        # driver init: set_timeout, enable_pkt, set_max_segment_size
        # (accl.py:374-380 <-> ccl_offload_control.c:1248-1279)
        self.set_timeout(timeout)
        self._config_call(CfgFunc.enable_pkt, 1)
        if max_segment_size is None:
            max_segment_size = device.preferred_segment_size()
            if tuner is not None:
                max_segment_size = tuner.recommend_segment_size(
                    max_segment_size)
        self.set_max_segment_size(max_segment_size)

    def _scratch(self, count: int, dtype) -> ACCLBuffer:
        """Reusable internal scratch buffer (e.g. gather relay)."""
        key = (count, np.dtype(dtype).name)
        if key not in self._scratch_bufs:
            self._scratch_bufs[key] = self.buffer((count,), dtype)
        return self._scratch_bufs[key]

    # -- lifecycle ---------------------------------------------------------
    @property
    def arith_registry(self) -> dict:
        """Arithmetic-config registry. Rebinding it invalidates the
        resolution memo; for IN-PLACE mutation call
        :meth:`invalidate_arith_cache` afterwards."""
        return self._arith_registry

    @arith_registry.setter
    def arith_registry(self, registry: dict):
        self._arith_registry = registry
        self._arith_memo.clear()

    def invalidate_arith_cache(self):
        """Drop memoized arith-config resolutions (call after mutating
        ``arith_registry`` in place)."""
        self._arith_memo.clear()

    @property
    def comm(self) -> Communicator:
        return self.communicators[0]

    @property
    def rank(self) -> int:
        return self.comm.local_rank

    @property
    def world_size(self) -> int:
        return self.comm.size

    def _config_call(self, fn: CfgFunc, value: int, comm_id: int = 0):
        """Issue an ACCL_CONFIG call through the full call path: the
        backend — not just the host — sees and applies the subfunction
        (reference: case ACCL_CONFIG, ccl_offload_control.c:1240-1283).
        Subfunction rides in ``tag``, value in ``count``."""
        self._call(CallDescriptor(CCLOp.config, count=int(value),
                                  comm_id=comm_id, tag=int(fn)),
                   run_async=False, waitfor=())

    def set_timeout(self, timeout: float):
        self._config_call(CfgFunc.set_timeout, int(round(timeout * 1000)))
        # client-side wait-budget bookkeeping (the SimDevice poll loop and
        # the in-process workers keep their own copy of the deadline)
        self.device.timeout = timeout

    def set_max_segment_size(self, nbytes: int):
        self._config_call(CfgFunc.set_max_segment_size, int(nbytes))

    def open_port(self):
        """Verify/arm the fabric listener (openPort parity, c:168-181)."""
        self._config_call(CfgFunc.open_port, 0)

    def init_connection(self, comm: Communicator | None = None):
        """Eagerly open sessions to every peer of ``comm`` (reference
        init_connection = open_port + open_con, accl.py driver bring-up;
        openCon c:109-165). Without it, the socket fabric dials lazily on
        first send — this pre-establishes, like the reference's TCP stack.
        """
        comm = comm or self.comm
        self._config_call(CfgFunc.open_port, 0, comm_id=comm.comm_id)
        self._config_call(CfgFunc.open_con, 0, comm_id=comm.comm_id)

    def close_connections(self):
        self._config_call(CfgFunc.close_con, 0)

    def set_stack_type(self, stack: str):
        """Runtime transport-stack select (HOUSEKEEP_SET_STACK_TYPE parity,
        c:1270-1272): 'tcp' or 'udp'. Every rank must switch while the
        fabric is quiesced."""
        code = {"tcp": 0, "udp": 1}[stack]
        self._config_call(CfgFunc.set_stack_type, code)

    def split_communicator(self, members: Sequence[int],
                           key: int = 0) -> Communicator:
        """Create and register a sub-communicator of world ranks ``members``.

        All member ranks must call this with the same ``members`` (the
        comm_id is derived deterministically from the membership, so members
        agree without a handshake; pass distinct ``key`` values to create
        multiple communicators over the same member set).
        """
        sub = self.comm.split(members, key=key)
        # splits inherit the driver's tenant grouping: a tenant's data-
        # parallel replicas and its sub-groups schedule/quota as ONE
        # tenant (accl_tpu/service)
        self.device.configure_communicator(sub, tenant=self.tenant)
        self._register_comm(sub)
        return sub

    # -- failure containment (ULFM-style revoke/shrink) --------------------
    def revoke(self, comm: Communicator | None = None):
        """Mark a communicator revoked: every later call on it raises
        ``PEER_FAILED`` immediately instead of rendezvousing with ranks
        that may be dead, and async handles ALREADY in flight on it
        abort with the typed error now (``device.abort_comm``) instead
        of riding out their full receive deadline. The application then
        rebuilds on the survivors via :meth:`shrink_communicator`.
        Rank-local (like the failure observation itself) — every
        surviving rank revokes when it observes
        ``ErrorCode.PEER_FAILED``; other communicators keep flowing
        untouched."""
        comm = comm or self.comm
        comm.revoked = True
        self.device.abort_comm(comm.comm_id, int(ErrorCode.PEER_FAILED))

    def shrink_communicator(self, dead_ranks: Sequence[int],
                            comm: Communicator | None = None,
                            key: int = 0x5A1D) -> Communicator:
        """Build and register the survivor communicator of ``comm``
        minus ``dead_ranks`` (GLOBAL ranks). Every surviving rank must
        call this with the same ``dead_ranks`` (the new comm_id derives
        deterministically from the survivor membership, like
        :meth:`split_communicator`); the dead ranks' channel state never
        carries over — the shrunken comm has fresh sequence spaces."""
        comm = comm or self.comm
        dead = {int(d) for d in dead_ranks}
        if comm.my_global_rank in dead:
            raise ValueError("cannot shrink away the local rank")
        survivors = [i for i, r in enumerate(comm.ranks)
                     if r.global_rank not in dead]
        if len(survivors) == len(comm.ranks):
            raise ValueError(f"no member of comm {comm.comm_id} is in "
                             f"dead_ranks {sorted(dead)}")
        sub = comm.split(survivors, key=key)
        self.device.configure_communicator(sub, tenant=self.tenant)
        self._register_comm(sub)
        METRICS.inc("membership_shrink_total", rank=self.rank)
        if TRACE.enabled:
            TRACE.emit("membership_shrink", rank=self.rank,
                       nbytes=len(survivors), peer=-1)
        return sub

    def grow_communicator(self, new_ranks: Sequence,
                          comm: Communicator | None = None,
                          base_members: Sequence[int] | None = None,
                          key: int = 0,
                          handshake_timeout: float | None = None,
                          retries: int | None = None,
                          retry_policy: "RetryPolicy | None" = None
                          ) -> Communicator:
        """Build, register, and bootstrap the grown communicator of
        ``comm`` plus ``new_ranks`` — the dual of
        :meth:`shrink_communicator`, and the recovery half of the
        elastic-membership story (the failure half is heartbeat
        detection + revoke + shrink).

        Every member of the NEW communicator — survivors and joiners —
        must call this with the same membership (SPMD, like every
        membership operation). Survivors pass their current (shrunken)
        communicator as ``comm``; a JOINER, which is not a member of
        that comm, instead passes ``base_members`` (the GLOBAL ranks of
        the communicator it is joining). ``new_ranks`` entries are
        global rank ints (addresses resolved from any registered
        communicator — the world comm knows everyone) or explicit
        :class:`~accl_tpu.communicator.Rank` records for ranks this
        driver has never seen.

        The grown membership is ordered by global rank and its comm_id
        derives deterministically from (membership, key), so all members
        agree without negotiation. When the grown membership+key matches
        an existing communicator (the canonical grow-back-to-the-world
        after a shrink), registration is a RE-configuration riding the
        existing epoch machinery: the device bumps its comm epoch (so no
        compiled plan of the old membership survives), the fabric drops
        the comm's retransmission channel state, and every member's seqn
        spaces restart at zero — stale ring/retx state is invalidated,
        never inherited.

        After configuring, a bootstrap JOIN handshake runs: every member
        announces itself (strm=JOIN hello frames carrying the membership
        signature) and waits for every peer, so no member can issue a
        collective on the grown comm before all members exist and agree
        — and a joiner that died (or never started) surfaces as a typed
        ``JOIN_FAILED`` instead of a first-collective deadline. The
        handshake is a retryable phase (``retries=``/``retry_policy=``,
        driver default otherwise): a slow joiner gets fresh attempts
        with the policy's uniform backoff. On final failure the grown
        comm is revoked (later calls on it refuse typed) and the error
        raises."""
        import time as _time
        if base_members is not None:
            if comm is not None:
                raise ValueError(
                    "pass either comm= or base_members=, not both (a "
                    "joiner names the membership it joins with "
                    "base_members; members pass their communicator)")
            base = [int(g) for g in base_members]
        else:
            comm = comm or self.comm
            base = [r.global_rank for r in comm.ranks]
        from .communicator import Rank, grown_communicator
        explicit: dict[int, Rank] = {}
        new_globals: list[int] = []
        for entry in new_ranks:
            if isinstance(entry, Rank):
                explicit[entry.global_rank] = entry
                new_globals.append(entry.global_rank)
            else:
                new_globals.append(int(entry))
        members = sorted(set(base) | set(new_globals))
        joiners = sorted(set(new_globals) - set(base))
        me = self.comm.my_global_rank
        if me not in members:
            raise ValueError(
                f"local rank (global {me}) is not a member of the grown "
                f"communicator {members} — joiners list themselves in "
                f"new_ranks or base_members")
        if not joiners:
            raise ValueError(
                f"nothing to grow: {sorted(set(new_globals))} are all "
                f"members of the base {sorted(set(base))} already")
        records = []
        for g in members:
            # explicit Rank records win; otherwise the driver's address
            # book — updated on EVERY registration, so the most recently
            # learned (host, port) for a global rank is authoritative
            # regardless of where its comm sits in the registry (a
            # reversed scan of self.communicators is NOT recency:
            # _register_comm replaces same-id comms in place, so a fresh
            # re-addressed record can live at an EARLIER index than a
            # stale one)
            rec = explicit.get(g) or self._rank_book.get(g)
            records.append(rec if rec is not None
                           else Rank(global_rank=g))
        grown = grown_communicator(records, me,
                                   mesh_axis=self.comm.mesh_axis,
                                   key=key)
        # register FIRST (riding the reconfiguration epoch machinery),
        # THEN handshake: each member sends its hello only after its own
        # seqn spaces and plan-cache epoch are fresh, so a peer that
        # completes the handshake and immediately issues a collective
        # can never race a member still carrying old-membership state
        self.device.configure_communicator(grown, tenant=self.tenant)
        policy = resolve_policy(retries, retry_policy, self.retry_policy)
        timeout = (handshake_timeout if handshake_timeout is not None
                   else getattr(self.device, "timeout", 5.0))
        attempt = 0
        while True:
            err = int(self.device.join_handshake(grown, timeout))
            if not err:
                break
            if policy is not None and policy.should_retry(err, attempt):
                METRICS.inc("membership_join_retries_total",
                            rank=self.rank)
                log.warning(
                    "rank %d: join handshake for grown comm %d failed "
                    "(0x%x) — retry %d", self.rank, grown.comm_id, err,
                    attempt + 1, extra={"rank": self.rank})
                _time.sleep(policy.backoff(attempt, grown.comm_id))
                attempt += 1
                continue
            grown.revoked = True
            METRICS.inc("membership_join_fail_total", rank=self.rank)
            raise ACCLError(err, f"grow_communicator{members}")
        self._register_comm(grown)
        METRICS.inc("membership_grow_total", rank=self.rank,
                    joiners=len(joiners))
        if TRACE.enabled:
            TRACE.emit("membership_grow", rank=self.rank,
                       nbytes=len(members), peer=-1)
        return grown

    def _register_comm(self, comm: Communicator):
        """Track a (re)built communicator, REPLACING any registered comm
        of the same id: after a grow-back the old-membership object (and
        its revoked flag) must not shadow the fresh one in comm_of().
        Every registration also refreshes the driver's per-global-rank
        address book — the recency source grow_communicator resolves
        member records from (list position is not recency: replacement
        happens in place)."""
        for r in comm.ranks:
            if r.global_rank >= 0:
                self._rank_book[r.global_rank] = r
        for i, c in enumerate(self.communicators):
            if c.comm_id == comm.comm_id:
                self.communicators[i] = comm
                return
        self.communicators.append(comm)

    def preflight(self, count: int, dtype=np.float32,
                  op: str = "allreduce",
                  comm: Communicator | None = None) -> list[str]:
        """Resource preflight for a planned collective: returns human-
        readable warnings (empty = clear). Today's one check is the
        PR-8 known issue: a hierarchical lowering of a multi-MiB call
        parks phase chunks in the finite rx pool, and ``nbufs*bufsize``
        below ~2 chunks degrades into timeout-shaped backpressure —
        surfaced here (and logged once per shape at hierarchical
        issue time) instead of discovered as a mystery deadline."""
        comm = comm or self.comm
        nbytes = int(count) * np.dtype(dtype).itemsize
        if comm is not self.comm or op not in HIERARCHICAL_OPS:
            return []
        return self._preflight_hier(op, nbytes)

    def _preflight_hier(self, op: str, nbytes: int) -> list[str]:
        """Price the FULL N-tier phase chain against the rx pool: each
        boundary tier's exchange parks blocks of roughly
        ``nbytes / groups(tier)`` in the finite pool, so coarser tiers
        (fewer groups, bigger blocks) are the first to breach the
        2-chunk rule — the warning names the offending tier."""
        cap_fn = getattr(self.device, "rx_capacity", None)
        hier = self._hier
        if cap_fn is None or hier is None:
            return []
        try:
            nbufs, bufsize = cap_fn()
        except Exception:  # noqa: BLE001 — preflight must never break
            return []      # the call it is trying to protect
        pool_bytes = nbufs * bufsize
        nest = getattr(hier, "nest", None) or (hier.groups,)
        warnings = []
        for k, grouping in enumerate(nest):
            n_groups = max(1, len(grouping))
            chunk = -(-nbytes // n_groups)
            if pool_bytes >= 2 * chunk:
                continue
            tier = "inter" if k == 0 else f"inter{k + 1}"
            unit = "hosts" if k == 0 else "groups"
            warnings.append(
                f"rx pool ({nbufs} x {bufsize} B = {pool_bytes} B) cannot "
                f"hold 2 chunks ({2 * chunk} B) of a hierarchical {op} of "
                f"{nbytes} B on tier {tier} ({n_groups} {unit}): expect "
                f"timeout-shaped backpressure — raise nbufs/bufsize or "
                f"split the call")
        return warnings

    # -- N-tier hierarchy (accl_tpu/hier) ----------------------------------
    def configure_hierarchy(self, hosts: Sequence[int],
                            levels: Sequence[Sequence[int]] = ()):
        """Declare the world's tier structure: ``hosts[r]`` is the host
        id of world rank ``r`` (each host's ranks contiguous), and each
        entry of ``levels`` adds one coarser boundary (rack, pod, ...)
        as another rank->group-id map, innermost-first. Builds the
        per-tier sub-communicators the HIERARCHICAL phase programs run
        over; every rank must configure the same mapping (sub-comm ids
        derive deterministically from membership, like
        :meth:`split_communicator`). Returns the
        :class:`~accl_tpu.hier.Hierarchy`."""
        from .hier import Hierarchy
        self._hier = Hierarchy(self, hosts, levels=levels)
        return self._hier

    @property
    def hierarchy(self):
        return self._hier

    def _ensure_hier(self):
        """Auto-configure the hierarchy once from an attached tuner's
        MeshTopology (the emu ``hosts=``/``outer_tiers=`` wiring and
        real deployments both land here) — deterministic across ranks,
        since every rank binds the same device topology. A mesh with
        coarser ``outer`` boundaries configures the full N-tier nest."""
        if self._hier is not None or not self._hier_autoprobe:
            return self._hier
        self._hier_autoprobe = False
        topo = getattr(self.tuner, "topology", None)
        groups = getattr(topo, "groups", None)
        if groups and len(groups) > 1 \
                and sum(len(g) for g in groups) == self.comm.size:
            from .hier import Hierarchy
            levels_fn = getattr(topo, "hosts_levels", None)
            if callable(levels_fn) and getattr(topo, "outer", ()):
                maps = levels_fn()
                self._hier = Hierarchy(self, maps[0], levels=maps[1:])
            else:
                self._hier = Hierarchy(self, topo.hosts_list())
        return self._hier

    def _hier_route(self, op: str, comm: Communicator, count: int,
                    elem_bytes: int, algorithm) -> bool:
        """True when this collective must lower to a hierarchical phase
        program instead of a flat descriptor. Explicit HIERARCHICAL
        demands a configured hierarchy over the world communicator;
        AUTO routes when the shared tuner's two-tier cost model says
        the phase program beats every flat schedule."""
        if isinstance(algorithm, str):
            algorithm = CollectiveAlgorithm[algorithm.upper()]
        alg = CollectiveAlgorithm(algorithm)
        H = CollectiveAlgorithm.HIERARCHICAL
        if alg == H:
            if self._ensure_hier() is None:
                raise ValueError(
                    "HIERARCHICAL requires a configured hierarchy: call "
                    "configure_hierarchy(hosts) on every rank (or attach "
                    "a tuner whose topology is a MeshTopology)")
            if comm is not self.comm:
                raise ValueError(
                    "hierarchical collectives run over the WORLD "
                    "communicator (the hierarchy's sub-communicators are "
                    "derived from it); got a split communicator")
            self._warn_preflight(op, count * elem_bytes)
            return True
        if (alg != CollectiveAlgorithm.AUTO or self.tuner is None
                or comm is not self.comm or op not in HIERARCHICAL_OPS):
            return False
        if self._parent_tag:
            # already inside a logical program (a redistribute's
            # internal allgather/alltoall, a hierarchy phase): stay
            # flat — nested hierarchical lowering would overwrite the
            # parent attribution tag and re-chain phases under a
            # different logical call
            return False
        if self._ensure_hier() is None:
            return False
        routed = self.tuner.select(op, comm.size,
                                   count * elem_bytes) == H
        if routed:
            self._warn_preflight(op, count * elem_bytes)
        return routed

    def _warn_preflight(self, op: str, nbytes: int):
        """Log the rx-pool preflight warnings once per (op, size) shape
        at hierarchical issue time (ACCL.preflight is the query form)."""
        key = (op, nbytes)
        if key in self._preflight_warned:
            return
        self._preflight_warned.add(key)
        for w in self._preflight_hier(op, nbytes):
            log.warning("rank %d preflight: %s", self.rank, w,
                        extra={"rank": self.rank})

    @contextlib.contextmanager
    def _retry_scope(self, retries, retry_policy):
        """Per-call ``retries=``/``retry_policy=`` for COMPOSITE calls
        (redistribute, hierarchical lowerings): their sub-calls are
        issued internally, so the per-call override becomes the driver
        default for the issuing scope (one driver is used from one
        thread at a time — the established driver threading contract)."""
        policy = resolve_policy(retries, retry_policy, self.retry_policy)
        prev = self.retry_policy
        self.retry_policy = policy
        try:
            yield
        finally:
            self.retry_policy = prev

    @contextlib.contextmanager
    def _attributed(self, tag: str):
        """Scope marking every call issued inside it as a phase of one
        logical call: their CallRecords carry ``parent=tag``."""
        prev = self._parent_tag
        self._parent_tag = tag
        try:
            yield
        finally:
            self._parent_tag = prev

    def soft_reset(self):
        """Rank-local soft reset through the call path (HOUSEKEEP_SWRST
        parity, c:1244-1247): drains the rx pool and zeroes seqnos."""
        self._config_call(CfgFunc.reset_periph, 0)

    # -- profiling (parity: start/end_profiling cfg calls,
    #    xlnx-consts.hpp:27-28; SURVEY §5 tracing subsystem) ----------------
    def start_profiling(self):
        """Enable per-call timing capture. Issues the config call through
        the full call path (backends arm their own counters — the socket
        daemons' profiled-call counts are visible via get_info), then arms
        the host-side recorder."""
        self._config_call(CfgFunc.start_profiling, 0)
        self.profiler.start()
        self._hold_spans(True)

    def end_profiling(self):
        self._config_call(CfgFunc.end_profiling, 0)
        self.profiler.stop()
        self._hold_spans(False)

    def _hold_spans(self, on: bool):
        """Arm the process's program spans (:data:`~accl_tpu.tracing.SPANS`)
        while this driver profiles: they stay on while any driver does."""
        if on != self._holds_spans:
            self._holds_spans = on
            if on:
                SPANS.arm()
            else:
                SPANS.disarm()

    # -- observability (SURVEY §5: the ILA-probe/waveform-dump analogs) ----
    def start_trace(self):
        """Arm the process-wide flight recorder
        (:data:`~accl_tpu.tracing.TRACE`): the streamed executor, egress
        stage, combine workers, RX pools and fabrics start emitting
        structured stage events into per-thread ring buffers. Also armed
        by ``ACCL_TPU_TRACE=1``. Near-free for everyone else: disarmed
        emit sites are a single attribute test."""
        TRACE.start()

    def stop_trace(self):
        TRACE.stop()

    def export_trace(self, path: str) -> int:
        """Write the flight recorder's current ring as Chrome/Perfetto
        trace-event JSON (open in chrome://tracing or ui.perfetto.dev;
        one track per lane/worker per rank). Returns the event count."""
        return TRACE.export_chrome(path)

    def metrics_snapshot(self) -> dict:
        """One process-wide health surface: every counter/gauge/histogram
        of :data:`~accl_tpu.tracing.METRICS` — per-call accounting
        (labeled op/comm_id), fabric counters (per communicator), RX-pool
        occupancy, executor pipeline gauges, plan-cache counters, daemon
        ingress rejections, tuner exploration picks — merged with every
        live registered collector's rows."""
        return METRICS.snapshot()

    def metrics_text(self) -> str:
        """Prometheus-style text exposition of :meth:`metrics_snapshot`
        (scrape-ready for the multi-tenant service story, ROADMAP 3)."""
        return METRICS.to_prometheus()

    def _metrics_rows(self):
        """Registry-collector rows for this driver's per-communicator
        call accounting (polled at snapshot time only). ``rank`` keeps
        one world's drivers apart; ``ctx`` (the emu fabric's instance
        tag, when the backend has one) keeps concurrently live same-shape
        worlds apart — their membership-CRC comm_ids collide."""
        labels = {"rank": self.rank}
        fab = getattr(getattr(self.device, "ctx", None), "fabric", None)
        ctx_seq = getattr(fab, "ctx_seq", None)
        if ctx_seq is not None:
            labels["ctx"] = ctx_seq
        # tenant attribution on the driver rows (PR 11): serving traffic
        # (put/get) is separable from collectives per tenant straight
        # from the exposition, without joining against CallRecords
        for (op, comm_id), n in list(self._call_counts.items()):
            yield ("counter", "accl_calls_total",
                   dict(labels, op=op, comm_id=comm_id,
                        tenant=self.tenant or f"comm-{comm_id}"), n)
        for (op, comm_id), n in list(self._byte_counts.items()):
            yield ("counter", "accl_bytes_total",
                   dict(labels, op=op, comm_id=comm_id,
                        tenant=self.tenant or f"comm-{comm_id}"), n)

    def deinit(self):
        # withdraw THIS driver's windows only — on a shared device
        # (multi-tenant) other tenants' registrations must survive
        for wid in list(self._windows):
            try:
                self.deregister_window(wid)
            except Exception:
                pass
        self._hold_spans(False)
        self.device.deinit()

    # -- buffers -----------------------------------------------------------
    def buffer(self, shape=None, dtype=np.float32, data=None,
               device_resident: bool = False) -> ACCLBuffer:
        """Allocate a device-registered buffer (reference: accl.buffer /
        pynq allocate).

        Pass a live ``jax.Array`` as ``data`` (or ``device_resident=True``
        with shape/dtype) for a device-resident buffer: TPU-backend calls
        then skip host staging entirely — the reference's
        ``to_from_fpga=False`` fast path. Backends without device arrays
        reject the request."""
        from .buffer import _is_jax_array
        if data is not None and _is_jax_array(data):
            data = self.device.adopt_device_array(data)
        elif device_resident:
            if data is not None:
                shape, dtype = np.shape(data), np.asarray(data).dtype
            data = self.device.make_device_array(shape, dtype, data)
        elif data is not None:
            data = np.ascontiguousarray(data)
            shape = data.shape
            dtype = data.dtype
        return ACCLBuffer(shape, dtype=dtype, device=self.device, data=data)

    # -- call plumbing -----------------------------------------------------
    def _resolve_wire(self, op: str, comm: Communicator, count: int,
                      operand_dtype, compress_dtype, block_scale):
        """Resolve ``compress_dtype="auto"``: the tuner prices the
        quantized wire variant (beta scaled by the wire-byte ratio plus
        the quant/dequant gamma term, tuner/cost.py) against the
        full-precision one and picks per (op, world, size) — fp8-e4m3
        block-scaled wire exactly in the bandwidth-bound band, no
        compression for latency-bound calls. Opt-in by the literal
        "auto": AUTO algorithm selection alone never changes numerics,
        and "auto" on a non-f32 call quietly stays uncompressed (the
        block-scaled lane is f32-only — crashing a call that runs fine
        uncompressed would make "auto" unsafe to sprinkle)."""
        if block_scale and compress_dtype is None:
            # the flat path raises this from _prepare; raising HERE too
            # keeps hierarchical lowerings (which never reach _prepare
            # with the caller's kwargs) from silently dropping the ask
            raise ValueError(
                "block_scale needs a compress_dtype naming the quantized "
                "wire dtype (int8 / float8_e4m3fn / float8_e5m2)")
        if not (isinstance(compress_dtype, str)
                and compress_dtype == "auto"):
            return compress_dtype, block_scale
        dt = None if operand_dtype is None else np.dtype(operand_dtype)
        if dt == np.dtype(np.float32) and self.tuner is not None \
                and self.tuner.select_wire(op, comm.size,
                                           count * dt.itemsize):
            import ml_dtypes
            return np.dtype(ml_dtypes.float8_e4m3fn), \
                (block_scale if block_scale else True)
        return None, False

    def _quant_block_for(self, count: int, elem_bytes: int,
                         block_scale) -> int:
        """The call's scale-block size: an explicit int is clamped into
        the legal envelope; ``True`` asks the tuner (falling back to the
        default) — larger blocks amortize the scale header, smaller ones
        track local dynamic range."""
        from . import quant
        if block_scale is True:
            if self.tuner is not None:
                return self.tuner.recommend_quant_block(
                    count * elem_bytes)
            return quant.DEFAULT_BLOCK
        return quant.clamp_block(int(block_scale))

    def _prepare(self, scenario: CCLOp, *, count: int, comm: Communicator,
                 root_src_dst: int = 0, func: ReduceFunc = ReduceFunc.SUM,
                 tag: int = TAG_ANY,
                 op0: ACCLBuffer | None = None, op1: ACCLBuffer | None = None,
                 res: ACCLBuffer | None = None,
                 compress_dtype: np.dtype | str | None = None,
                 block_scale: bool | int = False,
                 stream_dtype: np.dtype | str | None = None,
                 stream_flags: StreamFlags = StreamFlags.NO_STREAM,
                 algorithm: CollectiveAlgorithm | str = (
                     CollectiveAlgorithm.AUTO)
                 ) -> CallDescriptor:
        """Resolve dtypes to an arith config + compression flags.

        Parity: prepare_call (accl.py:528-592) — collect operand dtypes,
        find the matching arithmetic config, mark each narrower-typed
        operand OP{0,1}/RES_COMPRESSED, and request ETH_COMPRESSED when the
        caller asks for wire compression. ``block_scale`` (with
        ``compress_dtype``) upgrades the wire from plain narrowing to
        block-scaled quantization (accl_tpu/quant.py): True = tuner-
        recommended block size, an int = explicit block.
        """
        if getattr(comm, "revoked", False):
            # ULFM-style containment: a revoked communicator accepts no
            # further calls — the application shrinks to the survivors
            # (shrink_communicator) and rebuilds there
            raise ACCLError(int(ErrorCode.PEER_FAILED),
                            f"{scenario.name} on revoked communicator "
                            f"{comm.comm_id}")
        dtypes = {b.dtype for b in (op0, op1, res) if b is not None}
        compression = Compression.NONE
        if stream_dtype is not None:
            # streamed operands carry no buffer to resolve a dtype from —
            # without this a fully-streamed call silently coerces to f32
            dtypes.add(np.dtype(stream_dtype))
        if compress_dtype is not None:
            dtypes.add(np.dtype(compress_dtype))
            compression |= Compression.ETH_COMPRESSED
            if block_scale:
                compression |= Compression.BLOCK_SCALED
        elif block_scale:
            raise ValueError(
                "block_scale needs a compress_dtype naming the quantized "
                "wire dtype (int8 / float8_e4m3fn / float8_e5m2)")
        if not dtypes:
            dtypes = {np.dtype(np.float32)}
        # memoized: resolution walks name-sorted registry keys (~15us),
        # pure in its inputs, and on the per-call hot path. Rebinding
        # arith_registry clears the memo (property setter); in-place
        # registry mutation must call invalidate_arith_cache().
        # np.dtype hashes/compares in C — the dtype set is its own key.
        mk = frozenset(dtypes)
        cfg = self._arith_memo.get(mk)
        if cfg is None:
            cfg = resolve_arith_config(dtypes, self.arith_registry)
            self._arith_memo[mk] = cfg
        if compression & Compression.BLOCK_SCALED:
            # derive the block-scaled config (quant_block > 0 drives the
            # scale-header segmentation reserve + the executor's fused
            # dequant->accumulate->requant lane); memoized per (dtype
            # set, block) like the plain configs
            import dataclasses as _dc
            qblock = self._quant_block_for(
                count, cfg.uncompressed_elem_bytes, block_scale)
            bk = (mk, qblock)
            bcfg = self._arith_memo.get(bk)
            if bcfg is None:
                bcfg = _dc.replace(cfg, quant_block=qblock)
                self._arith_memo[bk] = bcfg
            cfg = bcfg
        elif (compression & Compression.ETH_COMPRESSED
                and cfg.is_compressing
                and cfg.compressed_dtype.kind in "iu"
                and cfg.uncompressed_dtype.kind == "f"):
            # fail at the call site, not deep in expansion: the
            # (float, int8) pair exists FOR the block-scaled lane —
            # plain astype narrowing truncates/wraps floats silently
            raise ValueError(
                f"compress_dtype={cfg.compressed_dtype.name} on "
                f"{cfg.uncompressed_dtype.name} operands requires "
                f"block-scaled quantization (pass block_scale=): plain "
                f"dtype narrowing to an integer wire would truncate")
        if cfg.is_compressing:
            if op0 is not None and op0.dtype == cfg.compressed_dtype:
                compression |= Compression.OP0_COMPRESSED
            if op1 is not None and op1.dtype == cfg.compressed_dtype:
                compression |= Compression.OP1_COMPRESSED
            if res is not None and res.dtype == cfg.compressed_dtype:
                compression |= Compression.RES_COMPRESSED
        if isinstance(algorithm, str):
            algorithm = CollectiveAlgorithm[algorithm.upper()]
        algorithm = CollectiveAlgorithm(algorithm)
        if (algorithm == CollectiveAlgorithm.AUTO and self.tuner is not None
                and scenario.name in VALID_ALGORITHMS):
            # resolve AUTO here so the concrete choice crosses the wire to
            # daemon/TPU tiers too (the engine-level fallback in
            # expand_call only covers in-process descriptors) — except for
            # ops the backend keeps for its own AUTO handling (the TPU
            # tier's 2D-tree rooted collectives, device.auto_resolvable_ops)
            resolvable = self.device.auto_resolvable_ops()
            if resolvable is None or scenario.name in resolvable:
                algorithm = self.tuner.select(
                    scenario.name, comm.size,
                    count * cfg.uncompressed_elem_bytes)
                if algorithm == CollectiveAlgorithm.HIERARCHICAL:
                    # safety net for paths that do not intercept the
                    # hierarchical route (barrier's internal allreduce,
                    # hierarchy phase calls): a flat descriptor carries
                    # a flat algorithm (accl_tpu/hier lowers
                    # HIERARCHICAL before a descriptor exists)
                    algorithm = DEFAULT_ALGORITHMS[scenario.name]
        return CallDescriptor(
            scenario=scenario, count=count, comm_id=comm.comm_id,
            root_src_dst=root_src_dst, function=func, tag=tag,
            arithcfg=cfg, compression=compression, stream_flags=stream_flags,
            algorithm=CollectiveAlgorithm(algorithm),
            addr_0=op0.address if op0 is not None else 0,
            addr_1=op1.address if op1 is not None else 0,
            addr_2=res.address if res is not None else 0)

    def _call(self, desc: CallDescriptor, run_async: bool,
              waitfor: Sequence[CallHandle], chain: bool = False,
              retries: int | None = None,
              retry_policy: "RetryPolicy | None" = None) -> CallHandle:
        """Issue a call, applying the resolved retry policy (per-call
        ``retries=``/``retry_policy=`` over the driver default). A retry
        is an epoch-scoped idempotent re-execution: the failed attempt
        advanced every per-peer seqn counter to its final value at
        admission, so the re-execution's frames live in a fresh seqn
        range stale traffic cannot satisfy; ``device.prepare_retry``
        purges the dead attempt's stranded rx frames; and the plan cache
        makes re-expansion free. Policies must be uniform across the
        ranks of a communicator (docs/ARCHITECTURE.md, Failure model).

        While spans are armed the call is the root span ``accl.call.<op>``:
        it takes an id and re-enters here once, inside the span."""
        if SPANS.enabled and not desc.span_call:
            desc.span_call = SPANS.next_call()
            cfg = desc.arithcfg
            with annotate(f"accl.call.{desc.scenario.name}",
                          call=desc.span_call, op=desc.scenario.name,
                          nbytes=desc.count * (cfg.uncompressed_elem_bytes
                                               if cfg is not None else 0),
                          comm=desc.comm_id):
                return self._call(desc, run_async, waitfor, chain,
                                  retries, retry_policy)
        policy = resolve_policy(retries, retry_policy, self.retry_policy)
        if (policy is None or policy.retries <= 0
                or desc.scenario == CCLOp.config):
            return self._call_once(desc, run_async, waitfor, chain)
        if run_async:
            return self._call_async_retry(desc, waitfor, chain, policy)
        import time as _time
        attempt = 0
        while True:
            try:
                return self._call_once(desc, run_async, waitfor, chain)
            except ACCLError as exc:
                if policy.should_retry(exc.error_word, attempt):
                    self._note_retry(desc, attempt, exc.error_word)
                    _time.sleep(policy.backoff(attempt, desc.comm_id))
                    attempt += 1
                    continue
                if attempt and policy.should_retry(exc.error_word, 0):
                    # retryable failure class, attempts exhausted: say so
                    raise ACCLError(
                        exc.error_word
                        | int(ErrorCode.CALL_RETRIES_EXHAUSTED),
                        desc.scenario.name) from exc
                raise

    def _note_retry(self, desc: CallDescriptor, attempt: int, word: int):
        METRICS.inc("call_retries_total", op=desc.scenario.name,
                    comm_id=desc.comm_id, rank=self.rank)
        if TRACE.enabled:
            TRACE.emit("call_retry", rank=self.rank, seqn=attempt,
                       nbytes=desc.count, peer=-1)
        log.warning(
            "rank %d: %s on comm %d failed (0x%x) — retry %d (fresh "
            "seqn epoch)", self.rank, desc.scenario.name, desc.comm_id,
            word, attempt + 1, extra={"rank": self.rank})
        prep = getattr(self.device, "prepare_retry", None)
        if prep is not None:
            try:
                prep(desc.comm_id)
            except Exception:  # noqa: BLE001 — cleanup is best-effort;
                pass           # the retry itself decides success

    def _call_async_retry(self, desc: CallDescriptor, waitfor,
                          chain: bool, policy: "RetryPolicy"
                          ) -> CallHandle:
        """Async form of the retry loop: the outer handle completes only
        when an attempt succeeds or the policy gives up; re-issues run
        off a timer thread (never on the backend's finish worker, whose
        sleep would stall other tenants' retirements)."""
        outer = CallHandle(context=desc.scenario.name)
        state = {"attempt": 0}

        def issue():
            try:
                inner = self._call_once(desc, True, waitfor, chain)
            except ACCLError as exc:
                # preserve the true error word: callers branch on it
                # (PEER_FAILED -> shrink, retryable -> their own backoff)
                outer.complete(exc.error_word, exception=exc)
                return
            except Exception as exc:  # noqa: BLE001 — surface, not hang
                outer.complete(int(ErrorCode.INVALID_CALL), exception=exc)
                return
            inner.add_done_callback(
                lambda err, h=inner: on_done(err, h))

        def on_done(err, inner):
            err = int(err)
            if err and policy.should_retry(err, state["attempt"]):
                a = state["attempt"]
                state["attempt"] = a + 1
                self._note_retry(desc, a, err)
                import threading as _threading
                t = _threading.Timer(policy.backoff(a, desc.comm_id),
                                     issue)
                t.daemon = True
                t.start()
                return
            if err and state["attempt"] and policy.should_retry(err, 0):
                err |= int(ErrorCode.CALL_RETRIES_EXHAUSTED)
            outer.complete(err, exception=inner._exception)

        issue()
        return outer

    def _call_once(self, desc: CallDescriptor, run_async: bool,
                   waitfor: Sequence[CallHandle],
                   chain: bool = False) -> CallHandle:
        import time as _time
        if chain and run_async:
            # cross-call pipelining hint (the C++ driver's call_chain
            # analog): the backend may admit this call's move program
            # while the predecessor drains — see CallDescriptor.chain
            desc.chain = True
        profiling = self.profiler.enabled and desc.scenario != CCLOp.config
        tunable = (desc.scenario.name in VALID_ALGORITHMS
                   and desc.algorithm != CollectiveAlgorithm.AUTO)
        # only unchained SYNCHRONOUS calls on a QUIET device train the
        # tuner: chained calls include predecessor wait time in their
        # issue->retire window, async calls queue behind each other on
        # the device worker, and a sync call issued while async work is
        # still in flight queues behind it too — any of these would
        # credit pipeline context, not algorithm speed, to the EWMA (the
        # Profiler keeps recording them all — attribution wants the full
        # window; training does not). Quiescence is checked across EVERY
        # driver sharing this tuner (tuner.quiescent()), not just this
        # one: multi-tenant worlds share one tuner, and another tenant's
        # concurrent storm inflating this call's window must not
        # cross-contaminate the EWMA stream.
        observing = (self.tuner is not None and tunable
                     and not run_async and not waitfor
                     and self._async_inflight == 0
                     and self.tuner.quiescent())
        t0 = _time.perf_counter() if (profiling or observing) else 0.0
        if run_async:
            # count the async call in flight BEFORE it launches: from the
            # moment call_async returns (or even mid-submission, on the
            # driver-bypass path) the storm is executing, and a sibling
            # driver checking tuner.quiescent() in that window must not
            # train on a wall clock this call is already inflating
            with self._async_mu:
                self._async_inflight += 1
            if self.tuner is not None:
                self.tuner.note_async_issue()
        try:
            handle = self.device.call_async(desc, waitfor,
                                            inline_ok=not run_async)
        except BaseException:
            if run_async:
                with self._async_mu:
                    self._async_inflight -= 1
                if self.tuner is not None:
                    self.tuner.note_async_retire()
            raise
        ebytes = (desc.arithcfg.uncompressed_elem_bytes
                  if desc.arithcfg is not None else 0)
        op = desc.scenario.name
        if desc.scenario != CCLOp.config:
            # per-communicator attribution: driver-local counters (see
            # __init__) — a registry lock here measurably skewed the
            # small-message algorithm ladder under 8 rank threads
            key = (op, desc.comm_id)
            self._call_counts[key] = self._call_counts.get(key, 0) + 1
            nb = desc.count * ebytes
            if nb:
                self._byte_counts[key] = \
                    self._byte_counts.get(key, 0) + nb
        if profiling:
            if tunable:
                alg_label = desc.algorithm.name
            elif op in VALID_ALGORITHMS:
                # AUTO descriptor: when the backend resolves every op's
                # AUTO through the shared engine path (emu/sim tiers),
                # the concrete default it will expand is knowable here —
                # record it so untuned-run history stays usable for
                # Tuner.ingest_records. Backends with internal AUTO
                # handling the enum cannot name (TPU 2D trees) get the
                # honest "AUTO" label instead.
                from .constants import DEFAULT_ALGORITHMS
                alg_label = (DEFAULT_ALGORITHMS[op].name
                             if (self.tuner is None and
                                 self.device.auto_resolvable_ops() is None)
                             else "AUTO")
            else:
                alg_label = ""
            self.profiler.attach(handle, op=op, count=desc.count,
                                 nbytes=desc.count * ebytes,
                                 comm_id=desc.comm_id, t0=t0,
                                 algorithm=alg_label,
                                 tenant=self.tenant
                                 or f"comm-{desc.comm_id}",
                                 parent=self._parent_tag)
        if observing:
            # retire-time measurement back to the tuner (same done-callback
            # path the profiler records through: async chains credit their
            # true issue->retire duration, not host dispatch time)
            tuner, op = self.tuner, desc.scenario.name
            world, nbytes = self.comm_of(desc.comm_id).size, \
                desc.count * ebytes
            alg = desc.algorithm
            quantized = bool(desc.compression & Compression.BLOCK_SCALED)

            def _feed(error_word: int, _t0=t0):
                dt = _time.perf_counter() - _t0
                tuner.observe(op, world, nbytes, alg, dt, error_word)
                # wire-variant refinement: measured quantized/plain
                # durations sharpen select_wire's cost-model crossover
                # (benchmarks/tune.py sweeps both legs deliberately)
                tuner.observe_wire(op, world, nbytes, quantized, dt,
                                   error_word)

            handle.add_done_callback(_feed)
        if run_async:
            # (in-flight counters were bumped BEFORE call_async above —
            # cross-driver visibility via tuner.quiescent() must cover
            # the launch window itself)
            comm_id = desc.comm_id

            def _retired(err):
                with self._async_mu:
                    self._async_inflight -= 1
                if self.tuner is not None:
                    self.tuner.note_async_retire()
                if err:
                    METRICS.inc("accl_call_errors_total", op=op,
                                comm_id=comm_id)

            handle.add_done_callback(_retired)
            return handle
        try:
            if SPANS.enabled and not handle.done():
                # a member waiting for its peers and the launch; the
                # launching rank and inline ops find theirs retired
                with annotate("accl.wait", call=desc.span_call,
                              group=desc.span_group):
                    handle.wait()
            else:
                handle.wait()
        except ACCLError:
            METRICS.inc("accl_call_errors_total", op=op,
                        comm_id=desc.comm_id)
            raise
        return CompletedHandle(context=desc.scenario.name)

    def comm_of(self, comm_id: int) -> Communicator:
        """Registered communicator by id (world or split)."""
        for c in self.communicators:
            if c.comm_id == comm_id:
                return c
        raise KeyError(f"no communicator with id {comm_id}")

    # -- tier-2 integrity: cross-rank result fingerprinting ----------------
    def _want_verify(self, explicit: bool | None, run_async: bool,
                     compressing: bool = False) -> bool:
        """Per-call ``verify_integrity=`` over the driver default. Sync
        calls only: verification is a follow-up collective issued from
        the calling thread — an explicit request on an async call is an
        error (silently skipping it would fake coverage), the driver
        default just doesn't apply there. Wire-compressed calls are
        likewise excluded: lossy dtype narrowing legitimately
        desynchronizes result BYTES across roles (a bcast root keeps
        its original-precision buffer while receivers hold the
        narrowed-then-widened values), so a byte fingerprint would
        raise a false DATA_INTEGRITY_ERROR on a perfectly healthy
        wire — the driver default skips them, an explicit request
        raises."""
        if explicit is None and self._parent_tag:
            # phases of a hierarchical/redistribute lowering: the
            # LOGICAL call verifies its final result once — per-phase
            # exchanges would multiply the cost without adding coverage
            return False
        want = self.verify_integrity if explicit is None else bool(explicit)
        if not want:
            return False
        if compressing:
            if explicit:
                raise ValueError(
                    "verify_integrity cannot cover a compress_dtype "
                    "call: lossy wire narrowing makes result bytes "
                    "legitimately differ across ranks (the root/owner "
                    "keeps original precision), so a fingerprint "
                    "mismatch would not mean corruption")
            return False
        if run_async:
            if explicit:
                raise ValueError(
                    "verify_integrity requires a synchronous call (the "
                    "fingerprint exchange is a follow-up collective on "
                    "the calling thread); wait the handle and verify "
                    "via a sync call, or use the driver-wide default")
            return False
        return True

    def fingerprint_of(self, buf: ACCLBuffer, nelems: int | None = None
                       ) -> int:
        """Cheap content fingerprint of a result buffer: crc32 over the
        first ``nelems`` elements' raw bytes. Exact across ranks because
        the execution engines hold collective results BIT-identical (the
        differential-test invariant) — equal data, equal fingerprint."""
        import zlib
        flat = np.ascontiguousarray(buf.data).reshape(-1)
        if nelems is not None:
            flat = flat[:nelems]
        return zlib.crc32(flat.view(np.uint8)) & 0xFFFFFFFF

    def _verify_result(self, op: str, buf: ACCLBuffer, nelems: int,
                       comm: Communicator):
        """The tier-2 cross-check: allgather every rank's result
        fingerprint (one int64 — the exchange rides the now-self-healing
        wire like any small collective) and compare. A disagreement
        means some rank's RESULT bytes differ — local combine/scratch/
        memory corruption, the class neither retransmission nor the wire
        checksum can see — and raises typed DATA_INTEGRITY_ERROR naming
        the minority rank(s)."""
        fp = self.fingerprint_of(buf, nelems)
        W = comm.size
        src = self._scratch(1, np.int64)
        dst = self._scratch(W, np.int64)
        src.data[0] = fp
        self.allgather(src, dst, 1, comm=comm, verify_integrity=False)
        fps = dst.data[:W].copy()
        if TRACE.enabled:
            TRACE.emit("fingerprint", rank=self.rank, seqn=comm.comm_id,
                       peer=-1, nbytes=int(fp))
        if (fps == fp).all():
            METRICS.inc("integrity_verified_total", op=op,
                        comm_id=comm.comm_id, rank=self.rank)
            return
        vals, counts = np.unique(fps, return_counts=True)
        if counts.max() * 2 > W:
            majority = vals[counts.argmax()]
            bad = [r for r in range(W) if fps[r] != majority]
            what = f"rank(s) {bad} disagree"
        else:
            # no STRICT majority (always the case at W=2, or an even
            # split): attributing the corruption to either side would
            # be a coin flip that steers an operator at the wrong host
            # half the time — name every rank and say so
            bad = list(range(W))
            what = (f"no majority fingerprint — the split is "
                    f"undecidable, any of rank(s) {bad} may hold the "
                    f"corrupt result")
        METRICS.inc("integrity_mismatch_total", op=op,
                    comm_id=comm.comm_id, rank=self.rank)
        log.error(
            "rank %d: %s result fingerprint mismatch on comm %d — "
            "%s (fingerprints %s). Local data "
            "corruption: NOT retried (a re-execution could mask it).",
            self.rank, op, comm.comm_id, what, [int(f) for f in fps],
            extra={"rank": self.rank})
        raise ACCLError(
            int(ErrorCode.DATA_INTEGRITY_ERROR),
            f"{op} on comm {comm.comm_id}: result fingerprint "
            f"mismatch — {what}")

    # -- primitives (parity: accl.py:738-985) ------------------------------
    def nop(self, run_async: bool = False, chain: bool = False,
            waitfor: Sequence[CallHandle] = (),
            retries: int | None = None,
            retry_policy: "RetryPolicy | None" = None
            ) -> CallHandle:
        """No-op through the full call path; used for call-latency probes
        (accl.py:738-745)."""
        return self._call(CallDescriptor(CCLOp.nop), run_async, waitfor,
                          chain, retries, retry_policy)

    def copy(self, srcbuf: ACCLBuffer | None, dstbuf: ACCLBuffer | None,
             count: int | None = None, *,
             comm: Communicator | None = None,
             stream_flags: StreamFlags = StreamFlags.NO_STREAM,
             stream_dtype=None, run_async: bool = False, chain: bool = False,
             waitfor: Sequence[CallHandle] = (),
             retries: int | None = None,
             retry_policy: "RetryPolicy | None" = None
             ) -> CallHandle:
        """Local copy. With OP0_STREAM the source is the rank's stream-in
        port (srcbuf may be None); with RES_STREAM the result goes to the
        stream-out port (dstbuf may be None) — the external-kernel data
        paths (reference: SWITCH_M_BYPASS / loopback plugin). A fully
        streamed copy takes its element type from ``stream_dtype``
        (default float32). ``comm`` scopes attribution/ordering only —
        no bytes cross the wire — and matters when the default comm is
        revoked: a reshard's local slice copies ride the EXCHANGE
        communicator, so elastic recovery works while the world comm is
        down (the whole point of revoke + shrink)."""
        if count is None:
            if srcbuf is not None:
                count = srcbuf.size
            elif dstbuf is not None:
                count = dstbuf.size
            else:
                raise ValueError("copy with both operands streamed "
                                 "requires an explicit count")
        desc = self._prepare(CCLOp.copy, count=count,
                             comm=comm or self.comm,
                             op0=srcbuf, res=dstbuf,
                             stream_dtype=stream_dtype,
                             stream_flags=stream_flags)
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    def combine(self, count: int, func: ReduceFunc, op0: ACCLBuffer | None,
                op1: ACCLBuffer, res: ACCLBuffer | None, *,
                stream_dtype=None,
                stream_flags: StreamFlags = StreamFlags.NO_STREAM,
                run_async: bool = False, chain: bool = False,
                waitfor: Sequence[CallHandle] = (),
                retries: int | None = None,
                retry_policy: "RetryPolicy | None" = None
                ) -> CallHandle:
        """With OP0_STREAM the first operand is sourced from this rank's
        stream-in port (op0 may be None); with RES_STREAM the result
        lands on the stream-out port (res may be None) — the
        combine-from-stream shape of the reference's plugin datapath."""
        desc = self._prepare(CCLOp.combine, count=count, comm=self.comm,
                             func=func, op0=op0, op1=op1, res=res,
                             stream_dtype=stream_dtype,
                             stream_flags=stream_flags)
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    def send(self, srcbuf: ACCLBuffer | None, count: int, dst: int,
             tag: int = TAG_ANY, *, comm: Communicator | None = None,
             compress_dtype=None, block_scale: bool | int = False,
             stream_dtype=None,
             stream_flags: StreamFlags = StreamFlags.NO_STREAM,
             run_async: bool = False, chain: bool = False,
             waitfor: Sequence[CallHandle] = (),
             retries: int | None = None,
             retry_policy: "RetryPolicy | None" = None
             ) -> CallHandle:
        """With OP0_STREAM the payload is sourced from this rank's
        stream-in port (srcbuf may be None; element type from
        ``stream_dtype``, default float32). ``block_scale`` (with
        ``compress_dtype``) sends block-scaled quantized wire segments
        — the receiver must post a matching block-scaled recv."""
        comm = comm or self.comm
        desc = self._prepare(CCLOp.send, count=count, comm=comm,
                             root_src_dst=dst, tag=tag, op0=srcbuf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale,
                             stream_dtype=stream_dtype,
                             stream_flags=stream_flags)
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    def recv(self, dstbuf: ACCLBuffer | None, count: int, src: int,
             tag: int = TAG_ANY, *, comm: Communicator | None = None,
             compress_dtype=None, block_scale: bool | int = False,
             stream_dtype=None,
             stream_flags: StreamFlags = StreamFlags.NO_STREAM,
             run_async: bool = False, chain: bool = False,
             waitfor: Sequence[CallHandle] = (),
             retries: int | None = None,
             retry_policy: "RetryPolicy | None" = None
             ) -> CallHandle:
        """With RES_STREAM the received payload lands on this rank's
        stream-out port instead of memory (dstbuf may be None; element
        type from ``stream_dtype``, default float32)."""
        comm = comm or self.comm
        desc = self._prepare(CCLOp.recv, count=count, comm=comm,
                             root_src_dst=src, tag=tag, res=dstbuf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale,
                             stream_dtype=stream_dtype,
                             stream_flags=stream_flags)
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    def stream_put(self, srcbuf: ACCLBuffer, count: int, dst: int,
                   tag: int = TAG_ANY, *, run_async: bool = False, chain: bool = False,
                   waitfor: Sequence[CallHandle] = (),
                   retries: int | None = None,
                   retry_policy: "RetryPolicy | None" = None
                   ) -> CallHandle:
        """Send into the remote rank's stream port instead of its rx pool
        (reference: remote-stream send, strm tag in the eth header)."""
        desc = self._prepare(CCLOp.send, count=count, comm=self.comm,
                             root_src_dst=dst, tag=tag, op0=srcbuf)
        desc.stream_flags |= StreamFlags.RES_STREAM
        # remote_stream is carried via tag on the move; device backends map
        # RES_STREAM on a send to strm delivery.
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    # -- one-sided RMA (accl_tpu/rma) --------------------------------------
    def register_window(self, buf: ACCLBuffer,
                        window: int | None = None) -> int:
        """Expose ``buf`` as a one-sided window peers can put/get
        against; returns the window id. Ids are the RMA address
        namespace and are exchanged at configure time: when every rank
        registers its windows in the same order, the auto-assigned ids
        agree across ranks without a handshake (pass ``window=`` to pin
        an explicit id instead). The buffer stays usable locally; a
        remote put lands in it with no local call posted."""
        if window is None:
            # counter skips ids pinned explicitly: an auto registration
            # silently stealing a pinned window would redirect every
            # later peer put/get at it into the wrong buffer
            wid = next(self._next_window)
            while wid in self._windows:
                wid = next(self._next_window)
        else:
            wid = int(window)
        self.device.register_window(wid, buf.address, buf.nbytes)
        self._windows[wid] = buf
        return wid

    def deregister_window(self, window: int):
        """Withdraw a window registration: later puts/gets against it
        fail typed (``RMA_WINDOW_ERROR``) at the initiator."""
        self.device.deregister_window(int(window))
        self._windows.pop(int(window), None)

    def put(self, srcbuf: ACCLBuffer, count: int, dst: int, window: int,
            offset: int = 0, *, comm: Communicator | None = None,
            compress_dtype=None, notify: int | None = None,
            run_async: bool = False,
            waitfor: Sequence[CallHandle] = (),
            retries: int | None = None,
            retry_policy: "RetryPolicy | None" = None) -> CallHandle:
        """One-sided write: ``count`` elements of ``srcbuf`` land at byte
        ``offset`` inside window ``window`` on rank ``dst`` (comm-local
        index), which posts NO matching call. Small payloads go eager
        (one frame riding the target's rx pool and tenant quotas); large
        ones rendezvous — RTS/CTS, then segments streamed directly into
        the window, never consuming the target's rx-pool buffers, so a
        multi-MiB KV-cache push cannot starve the pool its
        latency-critical collectives depend on. ``compress_dtype``
        narrows the wire dtype (decompress-on-landing). Completion (the
        data IS in the window) surfaces on the returned handle; chain
        behind compute with ``waitfor=``/``run_async=True``.

        ``notify=token`` (u64) makes the TARGET enqueue one completion
        record on its local notify queue when the put lands (or a typed
        error record when it fails there); the target discovers it with
        :meth:`poll_notifications` — one local dequeue, no collective.
        """
        comm = comm or self.comm
        desc = self._prepare(CCLOp.put, count=count, comm=comm,
                             root_src_dst=dst, tag=int(window), op0=srcbuf,
                             compress_dtype=compress_dtype)
        desc.addr_1 = int(offset)  # byte offset INTO the window (no
        # operand buffer rides addr_1 on one-sided calls)
        if notify is not None:
            # no result buffer rides addr_2 on a put, so it carries the
            # notify token to the device tier (0 = no notification)
            desc.addr_2 = int(notify) & 0xFFFFFFFFFFFFFFFF
        return self._call(desc, run_async, waitfor, False,
                          retries, retry_policy)

    def poll_notifications(self, window: int | None = None,
                           max_records: int = 64):
        """Drain this rank's put-with-notify completion queue: up to
        ``max_records`` :class:`~accl_tpu.rma.NotifyRecord` for
        ``window`` (all windows when None). Purely local — a direct
        device dequeue, not a descriptor call, so it issues NO
        collective and adds no ``accl_calls_total`` rows; a serving loop
        can poll it per decode step at zero wire cost."""
        from .rma.notify import ANY_WINDOW
        wid = ANY_WINDOW if window is None else int(window)
        return self.device.poll_notifications(wid, int(max_records))

    def get(self, dstbuf: ACCLBuffer, count: int, src: int, window: int,
            offset: int = 0, *, comm: Communicator | None = None,
            compress_dtype=None, run_async: bool = False,
            waitfor: Sequence[CallHandle] = (),
            retries: int | None = None,
            retry_policy: "RetryPolicy | None" = None) -> CallHandle:
        """One-sided read: ``count`` elements from byte ``offset`` of
        window ``window`` on rank ``src`` land in ``dstbuf``; the target
        posts no matching call. Same delivery machinery as :meth:`put`
        (the payload streams directly into ``dstbuf`` — requester-pulled
        transfers never buffer in either side's rx pool)."""
        comm = comm or self.comm
        desc = self._prepare(CCLOp.get, count=count, comm=comm,
                             root_src_dst=src, tag=int(window), res=dstbuf,
                             compress_dtype=compress_dtype)
        desc.addr_1 = int(offset)  # byte offset INTO the window
        return self._call(desc, run_async, waitfor, False,
                          retries, retry_policy)

    def stream_push(self, data) -> None:
        """Feed this rank's external-kernel stream-in port: the next call
        with OP0_STREAM sources its operand here (reference: the user
        kernel's AXIS port into the switch, SWITCH_S side)."""
        self.device.push_stream(data)

    def stream_pop(self, timeout: float = 0.0, count: int | None = None):
        """Read from this rank's stream-out port (reference: the AXIS port
        toward the user kernel): ``count`` elements — across however many
        RES_STREAM moves produced them, AXIS continuous-stream semantics —
        or the next entry whole when ``count`` is None. Waits up to
        ``timeout`` seconds; raises IndexError when it never fills."""
        return self.device.pop_stream(timeout, count)

    # -- collectives -------------------------------------------------------
    def bcast(self, buf: ACCLBuffer, count: int | None = None, root: int = 0,
              *, comm: Communicator | None = None,
                 algorithm: CollectiveAlgorithm | str = CollectiveAlgorithm.AUTO,
                 compress_dtype=None, block_scale: bool | int = False,
                 compress_phases: str | None = None,
              run_async: bool = False, chain: bool = False,
              waitfor: Sequence[CallHandle] = (),
              retries: int | None = None,
              retry_policy: "RetryPolicy | None" = None,
              verify_integrity: bool | None = None
              ) -> CallHandle:
        comm = comm or self.comm
        count = count if count is not None else buf.size
        compress_dtype, block_scale = self._resolve_wire(
            "bcast", comm, count, buf.dtype, compress_dtype,
            block_scale)
        routed = self._hier_route("bcast", comm, count,
                                  buf.dtype.itemsize, algorithm)
        if not routed and _phases_strip_flat(compress_phases):
            # strip BEFORE the verify decision (see allreduce)
            compress_dtype, block_scale = None, False
        verify = self._want_verify(verify_integrity, run_async,
                                   compress_dtype is not None)
        if routed:
            with self._retry_scope(retries, retry_policy):
                handle = self._hier.run("bcast", count=count, src=buf,
                                        root=root,
                                        compress_dtype=compress_dtype,
                                        block_scale=block_scale,
                                        compress_phases=compress_phases,
                                        run_async=run_async,
                                        waitfor=waitfor)
            if verify:
                self._verify_result("bcast", buf, count, comm)
            return handle
        desc = self._prepare(CCLOp.bcast, count=count, comm=comm,
                             root_src_dst=root, op0=buf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale,
                             algorithm=algorithm)
        handle = self._call(desc, run_async, waitfor, chain,
                            retries, retry_policy)
        if verify:
            self._verify_result("bcast", buf, count, comm)
        return handle

    def scatter(self, srcbuf: ACCLBuffer | None, dstbuf: ACCLBuffer,
                count: int, root: int = 0, *,
                comm: Communicator | None = None, compress_dtype=None,
                block_scale: bool | int = False,
                run_async: bool = False, chain: bool = False,
                waitfor: Sequence[CallHandle] = (),
                retries: int | None = None,
                retry_policy: "RetryPolicy | None" = None
                ) -> CallHandle:
        """count = per-rank chunk size; srcbuf holds world_size*count at
        root."""
        comm = comm or self.comm
        desc = self._prepare(CCLOp.scatter, count=count, comm=comm,
                             root_src_dst=root, op0=srcbuf, res=dstbuf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale)
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    def gather(self, srcbuf: ACCLBuffer, dstbuf: ACCLBuffer | None,
               count: int, root: int = 0, *,
               comm: Communicator | None = None,
                 algorithm: CollectiveAlgorithm | str = CollectiveAlgorithm.AUTO,
                 compress_dtype=None, block_scale: bool | int = False,
               run_async: bool = False, chain: bool = False,
               waitfor: Sequence[CallHandle] = (),
               retries: int | None = None,
               retry_policy: "RetryPolicy | None" = None
               ) -> CallHandle:
        """count = per-rank chunk; dstbuf holds world_size*count at root.
        Non-root ranks may pass None — a scratch relay buffer (the ring
        relay path, reference gather c:632-724) is allocated internally."""
        comm = comm or self.comm
        if comm.local_rank == root:
            if dstbuf is None:
                raise ValueError("gather root requires a destination buffer")
        elif dstbuf is None:
            dstbuf = self._scratch(count, srcbuf.dtype)
        desc = self._prepare(CCLOp.gather, count=count, comm=comm,
                             root_src_dst=root, op0=srcbuf, res=dstbuf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale,
                             algorithm=algorithm)
        if (desc.algorithm == CollectiveAlgorithm.TREE
                and comm.local_rank != root):
            # TREE gather relays a whole SUBTREE through non-root ranks,
            # not the ring's single chunk: upgrade an undersized scratch
            # (same dtype, so the prepared compression flags still hold)
            from .moveengine import tree_gather_scratch_chunks
            need = tree_gather_scratch_chunks(comm.size, comm.local_rank,
                                              root) * count
            if need and dstbuf.size < need:
                desc.addr_2 = self._scratch(need, dstbuf.dtype).address
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    def reduce(self, srcbuf: ACCLBuffer, dstbuf: ACCLBuffer | None, count: int,
               root: int = 0, func: ReduceFunc = ReduceFunc.SUM, *,
               comm: Communicator | None = None,
                 algorithm: CollectiveAlgorithm | str = CollectiveAlgorithm.AUTO,
                 compress_dtype=None, block_scale: bool | int = False,
               run_async: bool = False, chain: bool = False,
               waitfor: Sequence[CallHandle] = (),
               retries: int | None = None,
               retry_policy: "RetryPolicy | None" = None
               ) -> CallHandle:
        comm = comm or self.comm
        if comm.local_rank == root and dstbuf is None:
            raise ValueError("reduce root requires a destination buffer")
        desc = self._prepare(CCLOp.reduce, count=count, comm=comm,
                             root_src_dst=root, func=func, op0=srcbuf,
                             res=dstbuf, compress_dtype=compress_dtype,
                             block_scale=block_scale,
                             algorithm=algorithm)
        if (desc.algorithm == CollectiveAlgorithm.TREE
                and comm.local_rank != root
                and (dstbuf is None or dstbuf.size < count)):
            # TREE reduce accumulates child partials on internal ranks:
            # substitute an n-element accumulator scratch for an absent
            # OR undersized non-root dst (legal under RING/ROUND_ROBIN,
            # which never write it). Scratch is src-typed, so the RES
            # flag re-derives from the OP0 flag.
            desc.addr_2 = self._scratch(count, srcbuf.dtype).address
            desc.compression &= ~Compression.RES_COMPRESSED
            if desc.compression & Compression.OP0_COMPRESSED:
                desc.compression |= Compression.RES_COMPRESSED
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    def allgather(self, srcbuf: ACCLBuffer, dstbuf: ACCLBuffer, count: int, *,
                  comm: Communicator | None = None,
                 algorithm: CollectiveAlgorithm | str = CollectiveAlgorithm.AUTO,
                 compress_dtype=None, block_scale: bool | int = False,
                 compress_phases: str | None = None,
                  run_async: bool = False, chain: bool = False,
                  waitfor: Sequence[CallHandle] = (),
                  retries: int | None = None,
                  retry_policy: "RetryPolicy | None" = None,
                  verify_integrity: bool | None = None
                  ) -> CallHandle:
        comm = comm or self.comm
        compress_dtype, block_scale = self._resolve_wire(
            "allgather", comm, count,
            srcbuf.dtype if srcbuf.dtype == dstbuf.dtype else None,
            compress_dtype, block_scale)
        routed = self._hier_route(
            "allgather", comm, count,
            max(srcbuf.dtype.itemsize, dstbuf.dtype.itemsize),
            algorithm)
        if not routed and _phases_strip_flat(compress_phases):
            # strip BEFORE the verify decision (see allreduce)
            compress_dtype, block_scale = None, False
        verify = self._want_verify(verify_integrity, run_async,
                                   compress_dtype is not None)
        if routed:
            with self._retry_scope(retries, retry_policy):
                handle = self._hier.run("allgather", count=count,
                                        src=srcbuf, dst=dstbuf,
                                        compress_dtype=compress_dtype,
                                        block_scale=block_scale,
                                        compress_phases=compress_phases,
                                        run_async=run_async,
                                        waitfor=waitfor)
            if verify:
                self._verify_result("allgather", dstbuf,
                                    count * comm.size, comm)
            return handle
        desc = self._prepare(CCLOp.allgather, count=count, comm=comm,
                             op0=srcbuf, res=dstbuf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale,
                             algorithm=algorithm)
        handle = self._call(desc, run_async, waitfor, chain,
                            retries, retry_policy)
        if verify:
            # the replicated result is the whole gathered vector
            self._verify_result("allgather", dstbuf, count * comm.size,
                                comm)
        return handle

    def allreduce(self, srcbuf: ACCLBuffer, dstbuf: ACCLBuffer, count: int,
                  func: ReduceFunc = ReduceFunc.SUM, *,
                  comm: Communicator | None = None,
                 algorithm: CollectiveAlgorithm | str = CollectiveAlgorithm.AUTO,
                 compress_dtype=None, block_scale: bool | int = False,
                 compress_phases: str | None = None,
                  run_async: bool = False, chain: bool = False,
                  waitfor: Sequence[CallHandle] = (),
                  retries: int | None = None,
                  retry_policy: "RetryPolicy | None" = None,
                  verify_integrity: bool | None = None
                  ) -> CallHandle:
        """``compress_dtype`` narrows the wire; with ``block_scale``
        (True = tuner-recommended block, int = explicit) the wire is
        block-scale QUANTIZED instead — per-segment scale headers, f32
        accumulation, per-hop-bounded error (accl_tpu/quant.py).
        ``compress_dtype="auto"`` lets the tuner pick quantized wire in
        the bandwidth-bound band. ``compress_phases="inter"`` applies
        the wire compression only to phases that cross the slow
        inter-host tier of a HIERARCHICAL lowering (EQuARX's headline
        trick); intra-host phases stay full precision, and a flat call
        with "inter" is simply uncompressed."""
        comm = comm or self.comm
        compress_dtype, block_scale = self._resolve_wire(
            "allreduce", comm, count,
            srcbuf.dtype if srcbuf.dtype == dstbuf.dtype else None,
            compress_dtype, block_scale)
        routed = self._hier_route(
            "allreduce", comm, count,
            max(srcbuf.dtype.itemsize, dstbuf.dtype.itemsize),
            algorithm)
        if not routed and _phases_strip_flat(compress_phases):
            # strip BEFORE the verify decision: a flat "inter" call
            # executes fully uncompressed, where verification is valid
            compress_dtype, block_scale = None, False
        verify = self._want_verify(verify_integrity, run_async,
                                   compress_dtype is not None)
        if routed:
            with self._retry_scope(retries, retry_policy):
                handle = self._hier.run("allreduce", count=count,
                                        src=srcbuf, dst=dstbuf, func=func,
                                        compress_dtype=compress_dtype,
                                        block_scale=block_scale,
                                        compress_phases=compress_phases,
                                        run_async=run_async,
                                        waitfor=waitfor)
            if verify:
                self._verify_result("allreduce", dstbuf, count, comm)
            return handle
        desc = self._prepare(CCLOp.allreduce, count=count, comm=comm,
                             func=func, op0=srcbuf, res=dstbuf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale,
                             algorithm=algorithm)
        handle = self._call(desc, run_async, waitfor, chain,
                            retries, retry_policy)
        if verify:
            self._verify_result("allreduce", dstbuf, count, comm)
        return handle

    def reduce_scatter(self, srcbuf: ACCLBuffer, dstbuf: ACCLBuffer,
                       count: int, func: ReduceFunc = ReduceFunc.SUM, *,
                       comm: Communicator | None = None,
                 algorithm: CollectiveAlgorithm | str = CollectiveAlgorithm.AUTO,
                       compress_dtype=None, block_scale: bool | int = False,
                       compress_phases: str | None = None,
                       run_async: bool = False, chain: bool = False,
                       waitfor: Sequence[CallHandle] = (),
                       retries: int | None = None,
                       retry_policy: "RetryPolicy | None" = None
                       ) -> CallHandle:
        """count = per-rank chunk; srcbuf holds world_size*count."""
        comm = comm or self.comm
        compress_dtype, block_scale = self._resolve_wire(
            "reduce_scatter", comm, count,
            srcbuf.dtype if srcbuf.dtype == dstbuf.dtype else None,
            compress_dtype, block_scale)
        if self._hier_route(
                "reduce_scatter", comm, count,
                max(srcbuf.dtype.itemsize, dstbuf.dtype.itemsize),
                algorithm):
            with self._retry_scope(retries, retry_policy):
                return self._hier.run("reduce_scatter", count=count,
                                      src=srcbuf, dst=dstbuf, func=func,
                                      compress_dtype=compress_dtype,
                                      block_scale=block_scale,
                                      compress_phases=compress_phases,
                                      run_async=run_async, waitfor=waitfor)
        if _phases_strip_flat(compress_phases):
            compress_dtype, block_scale = None, False
        desc = self._prepare(CCLOp.reduce_scatter, count=count, comm=comm,
                             func=func, op0=srcbuf, res=dstbuf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale,
                             algorithm=algorithm)
        if desc.algorithm == CollectiveAlgorithm.RECURSIVE_DOUBLING:
            # the recursive-halving expansion needs a whole-vector
            # working buffer of partial sums (uncompressed dtype),
            # plumbed through the descriptor's otherwise-unused op1 slot
            desc.addr_1 = self._scratch(
                comm.size * count,
                desc.arithcfg.uncompressed_dtype).address
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    def alltoall(self, srcbuf: ACCLBuffer, dstbuf: ACCLBuffer, count: int, *,
                 comm: Communicator | None = None, compress_dtype=None,
                 block_scale: bool | int = False,
                 run_async: bool = False, chain: bool = False,
                 waitfor: Sequence[CallHandle] = (),
                 retries: int | None = None,
                 retry_policy: "RetryPolicy | None" = None
                 ) -> CallHandle:
        comm = comm or self.comm
        desc = self._prepare(CCLOp.alltoall, count=count, comm=comm,
                             op0=srcbuf, res=dstbuf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale)
        return self._call(desc, run_async, waitfor, chain,
                          retries, retry_policy)

    def alltoallv(self, srcbuf: ACCLBuffer, dstbuf: ACCLBuffer,
                  send_counts: Sequence[int], recv_counts: Sequence[int], *,
                  comm: Communicator | None = None, compress_dtype=None,
                  block_scale: bool | int = False,
                  run_async: bool = False, chain: bool = False,
                  waitfor: Sequence[CallHandle] = (),
                  retries: int | None = None,
                  retry_policy: "RetryPolicy | None" = None
                  ) -> CallHandle:
        """Variable-count all-to-all (MPI_Alltoallv, contiguous
        displacements): this rank sends ``send_counts[d]`` elements to
        rank d from the d-th interval of ``srcbuf`` (intervals tile the
        buffer in rank order) and receives ``recv_counts[s]`` elements
        from rank s into the s-th interval of ``dstbuf``. Count vectors
        must be pairwise consistent across ranks (rank i's
        ``send_counts[j]`` == rank j's ``recv_counts[i]``) — that is a
        cross-rank contract this driver cannot check locally; a mismatch
        surfaces as a recv deadline or a DMA size error on the shorter
        side. Zero-count peers exchange nothing (skewed MoE routing
        routinely zeroes most of the vector). ``compress_dtype=``/
        ``block_scale=`` ride the fp8 block-scaled wire exactly like the
        fixed-count collectives ("auto" prices the quantized wire via
        the tuner); ``chain=``/``waitfor=`` compose with the plan cache
        so repeated uneven exchanges pipeline behind compute.

        Overlapping ``srcbuf``/``dstbuf`` (in-place) are staged through
        a scratch copy of the send region: uneven intervals can alias
        across DIFFERENT peers' chunks, which no lane-local hazard edge
        can order, so the engine is only ever given disjoint regions."""
        comm = comm or self.comm
        W = comm.size
        send_counts = tuple(int(c) for c in send_counts)
        recv_counts = tuple(int(c) for c in recv_counts)
        if len(send_counts) != W or len(recv_counts) != W:
            raise ValueError(
                f"alltoallv count vectors must have comm.size={W} "
                f"entries; got {len(send_counts)} send / "
                f"{len(recv_counts)} recv")
        if min(send_counts + recv_counts) < 0:
            raise ValueError("alltoallv counts must be non-negative")
        n_send, n_recv = sum(send_counts), sum(recv_counts)
        if srcbuf.size < n_send or dstbuf.size < n_recv:
            raise ValueError(
                f"count vectors overflow their buffers: send needs "
                f"{n_send} elems (srcbuf {srcbuf.size}), recv needs "
                f"{n_recv} (dstbuf {dstbuf.size})")
        count = max(n_send, n_recv)
        compress_dtype, block_scale = self._resolve_wire(
            "alltoallv", comm, count,
            srcbuf.dtype if srcbuf.dtype == dstbuf.dtype else None,
            compress_dtype, block_scale)
        # uneven-exchange observability (docs/OBSERVABILITY.md): the
        # count-vector shape is what distinguishes this op — record the
        # port bytes and the skew (largest peer chunk over the even
        # share) so a routing collapse (all tokens to one expert rank)
        # is visible without a trace
        METRICS.inc("alltoallv_total", rank=self.rank)
        METRICS.inc("alltoallv_bytes_total",
                    count * srcbuf.dtype.itemsize, rank=self.rank)
        zero_peers = (sum(1 for c in send_counts if not c)
                      + sum(1 for c in recv_counts if not c))
        if zero_peers:
            METRICS.inc("alltoallv_zero_peers_total", zero_peers,
                        rank=self.rank)
        if count:
            cmax = max(max(send_counts), max(recv_counts))
            METRICS.set_gauge("alltoallv_skew",
                              round(cmax * W / count, 3), rank=self.rank)
        src_arena = srcbuf
        stage_pool = None
        a0, a1 = srcbuf.address, srcbuf.address + srcbuf.nbytes
        b0, b1 = dstbuf.address, dstbuf.address + dstbuf.nbytes
        if n_send and a0 < b1 and b0 < a1:
            if run_async:
                # private recycled stage (the redistribute pool): a
                # cached scratch would be shared by a second in-flight
                # exchange whose staging copy could overwrite bytes this
                # call's sends are still reading
                pk = (srcbuf.size, srcbuf.dtype.name)
                stage_pool = self._redist_stage_pool.setdefault(pk, [])
                src_arena = stage_pool.pop() if stage_pool else \
                    self.buffer((srcbuf.size,), srcbuf.dtype)
            else:
                src_arena = self._scratch(srcbuf.size, srcbuf.dtype)
            cp = self.copy(srcbuf[0:n_send], src_arena[0:n_send], n_send,
                           comm=comm, run_async=True, waitfor=waitfor)
            waitfor = (cp,)
        desc = self._prepare(CCLOp.alltoallv, count=count, comm=comm,
                             op0=src_arena, res=dstbuf,
                             compress_dtype=compress_dtype,
                             block_scale=block_scale)
        desc.counts = (send_counts, recv_counts)
        ret = self._call(desc, run_async, waitfor, chain,
                         retries, retry_policy)
        if stage_pool is not None:
            pool, buf = stage_pool, src_arena
            ret.add_done_callback(lambda _err: pool.append(buf))
        return ret

    def redistribute(self, srcbuf: ACCLBuffer, src_spec,
                     dstbuf: ACCLBuffer, dst_spec, *,
                     comm: Communicator | None = None,
                     members: Sequence[int] | None = None,
                     compress_dtype=None, run_async: bool = False,
                     waitfor: Sequence[CallHandle] = (),
                     retries: int | None = None,
                     retry_policy: "RetryPolicy | None" = None
                     ) -> CallHandle:
        """Change an array's sharding: ``srcbuf`` holds this rank's
        shard under ``src_spec`` (:class:`~accl_tpu.hier.ShardSpec`),
        and on completion ``dstbuf`` holds its shard under ``dst_spec``.

        The compiler (accl_tpu/hier/redistribute.py) lowers the spec
        pair to the minimal program the change admits — local slice
        copies, one allgather, one alltoall, one alltoallv (dense
        uneven block exchanges), or rotated point-to-point sends — and
        this driver executes it over ``comm`` (default: the
        world). ``members`` restricts the exchange to a world-rank
        subset: the driver derives (and caches) the sub-communicator,
        and both specs must span ``len(members)`` ranks. Overlapping
        src/dst buffers (in-place resharding) are staged through a
        scratch copy of the source shard. Every issued sub-call's
        CallRecord carries this logical call's tag as ``parent``."""
        import time as _time

        from .hier import plan_redistribute
        if members is not None:
            if comm is not None:
                # mutually exclusive in effect: members derives its own
                # sub-communicator, which would silently bypass the
                # passed comm (and any tenant/QoS state on it)
                raise ValueError(
                    "pass either comm= or members=, not both (members "
                    "derives its own sub-communicator of those world "
                    "ranks)")
            members = tuple(int(m) for m in members)
            comm = self._redist_comms.get(members)
            if comm is None:
                comm = self.split_communicator(list(members), key=0x52ED)
                self._redist_comms[members] = comm
        else:
            comm = comm or self.comm
        if src_spec.world != comm.size or dst_spec.world != comm.size:
            raise ValueError(
                f"spec worlds ({src_spec.world}, {dst_spec.world}) do "
                f"not match the communicator size {comm.size}")
        if srcbuf.dtype != dstbuf.dtype:
            raise ValueError(
                f"redistribute moves bytes, not values: src dtype "
                f"{srcbuf.dtype.name} != dst dtype {dstbuf.dtype.name} "
                f"(use compress_dtype for wire compression)")
        me = comm.local_rank
        src_count = src_spec.local_count(me)
        dst_count = dst_spec.local_count(me)
        if srcbuf.size < src_count or dstbuf.size < dst_count:
            raise ValueError(
                f"shard does not fit its buffer: src needs {src_count} "
                f"elems (buffer {srcbuf.size}), dst needs {dst_count} "
                f"(buffer {dstbuf.size})")
        pk = (src_spec, dst_spec, me)
        plan = self._redist_plans.get(pk)
        if plan is None:
            plan = plan_redistribute(src_spec, dst_spec, me)
            self._redist_plans[pk] = plan
        tag = f"redist#{next(self._redist_seq)}"
        key = ("redistribute", comm.comm_id)
        self._call_counts[key] = self._call_counts.get(key, 0) + 1
        # reshard observability (elastic membership rides on these):
        # rare-by-construction direct registry writes, like the fabric
        # fault counters — a reshard is a membership-scale event, not a
        # per-frame hot path
        nbytes_global = src_spec.n * srcbuf.dtype.itemsize
        METRICS.inc("reshard_total", rank=self.rank, kind=plan.kind)
        METRICS.inc("reshard_bytes_total", nbytes_global, rank=self.rank)
        if TRACE.enabled:
            TRACE.emit("reshard", rank=self.rank, nbytes=nbytes_global,
                       peer=-1)
        t0 = _time.perf_counter()

        def _slice(buf, off, n):
            if off == 0 and n == buf.size:
                return buf
            return buf[off:off + n]

        # validate shapes BEFORE issuing anything, and UNIFORMLY across
        # ranks: plans differ per rank (one rank's slices, another's
        # whole-buffer transfers), so a slicing-aware rank-local check
        # would raise on some ranks while their peers sail into recvs
        # that only fail by timeout — and the p2p program's eager sends
        # complete into peer rx pools, where a mid-program abort would
        # strand frames for a later TAG_ANY transfer to mis-match.
        # Hence the blanket contract: shard buffers are 1-D (flat
        # element layout).
        if plan.kind != "noop" and (len(srcbuf.shape) != 1
                                    or len(dstbuf.shape) != 1):
            raise ValueError(
                "redistribute addresses sub-ranges of the shard "
                "buffers; pass 1-D buffers (flat element layout)")

        # in-place resharding: stage the source shard so no transfer
        # reads bytes another transfer of the same program rewrites
        src_arena = srcbuf
        a0, a1 = srcbuf.address, srcbuf.address + srcbuf.nbytes
        b0, b1 = dstbuf.address, dstbuf.address + dstbuf.nbytes
        stage_pool = None
        if plan.kind != "noop" and a0 < b1 and b0 < a1:
            if run_async:
                # a cached stage would be shared by a second async
                # redistribute of the same shard size whose staging copy
                # could overwrite bytes the first call's sends (on a
                # DIFFERENT communicator — no FIFO ordering between
                # them) are still reading; async in-place reshards draw
                # a private buffer from a recycled pool (a fresh alloc
                # per call would grow the device-registered memory
                # without bound — buffers are returned by the program's
                # completion callback below)
                pk2 = (srcbuf.size, srcbuf.dtype.name)
                stage_pool = self._redist_stage_pool.setdefault(pk2, [])
                stage = stage_pool.pop() if stage_pool else \
                    self.buffer((srcbuf.size,), srcbuf.dtype)
            else:
                sk = ("redist-stage", srcbuf.size, srcbuf.dtype.name)
                stage = self._scratch_bufs.get(sk)
                if stage is None:
                    stage = self.buffer((srcbuf.size,), srcbuf.dtype)
                    self._scratch_bufs[sk] = stage
            src_arena = stage
        handles: list[CallHandle] = []
        with self._retry_scope(retries, retry_policy), \
                self._attributed(tag):
            if src_arena is not srcbuf and src_count:
                handles.append(self.copy(
                    _slice(srcbuf, 0, src_count),
                    _slice(src_arena, 0, src_count), src_count,
                    comm=comm, run_async=True, waitfor=waitfor))
                waitfor = (handles[-1],)
            if plan.kind == "allgather":
                handles.append(self.allgather(
                    _slice(src_arena, 0, src_count), dstbuf,
                    plan.coll_count, comm=comm,
                    compress_dtype=compress_dtype, run_async=True,
                    waitfor=waitfor))
            elif plan.kind == "alltoall":
                handles.append(self.alltoall(
                    _slice(src_arena, 0, src_count),
                    _slice(dstbuf, 0, dst_count), plan.coll_count,
                    comm=comm, compress_dtype=compress_dtype,
                    run_async=True, waitfor=waitfor))
            elif plan.kind == "alltoallv":
                # dense uneven reshard: the whole interval-ownership
                # program is one variable-count collective (the plan's
                # vectors tile the shards by construction, and src was
                # staged above if in-place, so the collective never
                # sees aliasing buffers)
                handles.append(self.alltoallv(
                    _slice(src_arena, 0, src_count),
                    _slice(dstbuf, 0, dst_count),
                    plan.send_counts, plan.recv_counts,
                    comm=comm, compress_dtype=compress_dtype,
                    run_async=True, waitfor=waitfor))
            else:
                for st in plan.steps:
                    if st.kind == "send":
                        handles.append(self.send(
                            _slice(src_arena, st.src_off, st.count),
                            st.count, dst=st.peer, comm=comm,
                            compress_dtype=compress_dtype,
                            run_async=True, waitfor=waitfor))
                    elif st.kind == "recv":
                        handles.append(self.recv(
                            _slice(dstbuf, st.dst_off, st.count),
                            st.count, src=st.peer, comm=comm,
                            compress_dtype=compress_dtype,
                            run_async=True, waitfor=waitfor))
                    else:
                        handles.append(self.copy(
                            _slice(src_arena, st.src_off, st.count),
                            _slice(dstbuf, st.dst_off, st.count),
                            st.count, comm=comm, run_async=True,
                            waitfor=waitfor))
        if run_async:
            if not handles:
                # nothing to issue (noop plan) — but the returned handle
                # must still carry the caller's waitfor ordering, like
                # the sync path's wait_all(waitfor) does
                handles = list(waitfor)
            if not handles:
                return CompletedHandle(context="redistribute")
            if len(handles) == 1:
                ret = handles[0]
            else:
                # no single sub-call handle is guaranteed last: the
                # device's FIFO retirement contract is per-comm
                # SUBMISSION order, but local copies may retire inline
                # while transfers drain on workers. Aggregate: complete
                # when EVERY sub-call has, with the OR of their error
                # words (first exception kept).
                import threading as _threading
                agg = CallHandle(context="redistribute")
                mu = _threading.Lock()
                state = {"left": len(handles), "err": 0, "exc": None}

                def _one_done(h):
                    def cb(err):
                        with mu:
                            state["err"] |= int(err)
                            if state["exc"] is None \
                                    and h._exception is not None:
                                state["exc"] = h._exception
                            state["left"] -= 1
                            done = state["left"] == 0
                        if done:
                            agg.complete(state["err"],
                                         exception=state["exc"])
                    return cb

                for h in handles:
                    h.add_done_callback(_one_done(h))
                ret = agg
            if stage_pool is not None:
                # recycle the private stage only when the WHOLE program
                # has retired (the aggregate — a single sub-call handle
                # could complete while a transfer on the other
                # communicator still reads the stage)
                pool, buf = stage_pool, src_arena
                ret.add_done_callback(lambda _err: pool.append(buf))
            return ret
        from .call import wait_all
        wait_all(handles if handles else list(waitfor))
        if self.profiler.enabled:
            from .tracing import CallRecord
            self.profiler.record(CallRecord(
                op="redistribute", count=src_spec.n,
                nbytes=src_spec.n * srcbuf.dtype.itemsize,
                comm_id=comm.comm_id, t_start=t0,
                duration_s=_time.perf_counter() - t0,
                algorithm=plan.kind.upper(), parent=tag,
                tenant=self.tenant or f"comm-{comm.comm_id}"))
        return CompletedHandle(context="redistribute")

    def barrier(self, *, comm: Communicator | None = None,
                waitfor: Sequence[CallHandle] = (),
                retries: int | None = None,
                retry_policy: "RetryPolicy | None" = None
                ) -> CallHandle:
        """Rendezvous of all ranks: a 1-element allreduce on a scratch
        buffer (the reference leans on host-side MPI barriers; we make it a
        first-class op)."""
        comm = comm or self.comm
        if self._barrier_buf is None:
            self._barrier_buf = self.buffer((2,), np.float32)
        buf = self._barrier_buf
        desc = self._prepare(CCLOp.allreduce, count=1, comm=comm,
                             op0=buf[0:1], res=buf[1:2])
        return self._call(desc, False, waitfor, False, retries,
                          retry_policy)

    # -- introspection (parity: accl.py:412-526, 710-735) ------------------
    def plan_cache_stats(self) -> dict:
        """Compiled-plan cache counters of this rank's backend (hits,
        misses, bypasses, evictions, per-reason invalidations), or an
        ``{"enabled": False}`` stub on backends without a plan cache.
        Per-call hit/miss/bypass is also on every profiled
        :class:`~accl_tpu.tracing.CallRecord` (``plan_cache`` field)."""
        cache = getattr(self.device, "plan_cache", None)
        if cache is None:
            return {"enabled": False, "entries": 0, "hits": 0, "misses": 0,
                    "bypasses": 0, "evictions": 0, "invalidations": {}}
        return cache.stats()

    def dump_communicator(self) -> str:
        return self.comm.describe()

    def dump_rx_buffers(self) -> str:
        pool = getattr(self.device, "pool", None)
        return pool.describe() if pool is not None else "<no rx pool>"
