"""Call plans (``ACCL._prepare``): the driver resolves a call signature
once and builds each later call's descriptor from its plan.

- The differential corpus: for every public collective and local op, every
  dtype pair of the arith registry, wire compression and block scaling,
  each algorithm, both roots and a split communicator, the descriptor a
  plan hit issues at another count, root, reduce function, tag and peer
  equals the one a fresh resolution builds for that call, field for field,
  on every tier.
- Hits give results bit-identical to a fresh driver.
- State that can change between two calls of one signature: a tuner's
  AUTO, the "auto" wire, a hierarchy, integrity verification, a phase of a
  logical call, a revoked communicator, a changed arith registry.
- Counting: every kind of call uses the plan, the cap,
  ``accl_call_plan_total``; errors, timeouts, profiler records and tuner
  observations of planned calls.
"""

import dataclasses

import ml_dtypes
import numpy as np
import pytest

import accl_tpu.accl as accl_mod
from accl_tpu.arith import DEFAULT_ARITH_CONFIGS, ArithConfig
from accl_tpu.call import CallDescriptor, CompletedHandle
from accl_tpu.constants import (ACCLError, CCLOp, CollectiveAlgorithm,
                                Compression, ErrorCode, ReduceFunc, StreamFlags,
                                VALID_ALGORITHMS)
from accl_tpu.testing import emu_world, run_ranks, sim_world
from accl_tpu.tracing import METRICS
from accl_tpu.tuner import Topology, Tuner

N = 8
W = 4
H = CollectiveAlgorithm.HIERARCHICAL
EMU_TOPO = Topology(world_size=2, alpha_us=20.0, beta_gbps=4.0, tier="emu")


def _world(tier: str, n: int = W):
    if tier == "emu":
        return emu_world(n)
    if tier == "sim":
        return sim_world(n)
    from accl_tpu.device.tpu import tpu_world
    return tpu_world(n)


def _deinit(accls):
    for a in accls:
        a.deinit()


# -- the differential corpus ----------------------------------------------

def _wires():
    """(operand dtype, result dtype, compress_dtype, block_scale,
    compress_phases) for every dtype pair of the registry: the plain pair,
    and for a compressing pair mixed operands, the narrowed wire, the
    block-scaled wire, and the narrowed wire only between hosts ("inter",
    which a flat call drops)."""
    out = []
    for (u, c) in sorted(DEFAULT_ARITH_CONFIGS):
        if u == c:
            out.append((u, u, None, False, None))
            continue
        out += [(u, c, None, False, None), (u, u, c, False, None),
                (u, u, c, True, None), (u, u, np.dtype(c), 64, None),
                (u, u, c, False, "inter")]
    return out


def _algorithms(op):
    algs = sorted(VALID_ALGORITHMS[op] - {H})
    # the enum default, each name, and one spelled as a string
    return ([CollectiveAlgorithm.AUTO] + algs
            + [algs[0].name.lower()])


class _Variant:
    """The per-call inputs a plan must not keep: a case's first call (0)
    and its hit (1) differ in each of them."""

    def __init__(self, v: int, comm):
        w, me = comm.size, comm.local_rank
        self.n = (N, N // 2)[v]
        self.root = (0, w - 1)[v]
        # roots other than this rank, for a non-root's call
        self.other = ((me + 1) % w, (me + 2) % w)[v]
        self.func = (ReduceFunc.SUM, ReduceFunc.MAX)[v]
        self.peer = ((me + 1) % w, (me + w - 1) % w)[v]
        self.tag = (7, 9)[v]
        self.window, self.offset = ((3, 16), (4, 8))[v]
        self.notify = (9, None)[v]
        self.counts = (tuple(range(1, w + 1)),
                       tuple(range(w, 0, -1)))[v]


def _corpus(op, comm):
    """Per case: a function of (driver, buffer set, variant) that issues
    one call of ``op`` on ``comm``. A buffer set is
    ``bufs(dtype, size, slot=0)``; buffers hold N elements per rank and a
    variant's count is at most N."""
    w = comm.size
    me = comm.local_rank
    cases = []
    phased = op in ("allreduce", "allgather", "reduce_scatter", "bcast")
    for u, c, wire, bs, phases in _wires():
        if phases and not phased:
            continue
        kw = dict(comm=comm, compress_dtype=wire, block_scale=bs)
        if phases:
            kw["compress_phases"] = phases
        if op == "allreduce":
            for alg in _algorithms(op):
                cases.append(lambda a, b, v, alg=alg, kw=kw, u=u, c=c:
                             a.allreduce(b(u, N), b(c, N), v.n, v.func,
                                         algorithm=alg, **kw))
        elif op == "allgather":
            for alg in _algorithms(op):
                cases.append(lambda a, b, v, alg=alg, kw=kw, u=u, c=c:
                             a.allgather(b(u, N), b(c, w * N), v.n,
                                         algorithm=alg, **kw))
        elif op == "reduce_scatter":
            for alg in _algorithms(op):
                cases.append(lambda a, b, v, alg=alg, kw=kw, u=u, c=c:
                             a.reduce_scatter(b(u, w * N), b(c, N), v.n,
                                              v.func, algorithm=alg, **kw))
        elif op == "bcast":
            for alg in _algorithms(op):
                cases.append(lambda a, b, v, alg=alg, kw=kw, u=u:
                             a.bcast(b(u, N), v.n, root=v.root,
                                     algorithm=alg, **kw))
        elif op == "reduce":
            for alg in _algorithms(op):
                # a result buffer on every rank, and none on a non-root
                cases.append(
                    lambda a, b, v, alg=alg, kw=kw, u=u, c=c:
                    a.reduce(b(u, N), b(c, N), v.n, root=v.root,
                             func=v.func, algorithm=alg, **kw))
                cases.append(
                    lambda a, b, v, alg=alg, kw=kw, u=u:
                    a.reduce(b(u, N), None, v.n, root=v.other,
                             func=v.func, algorithm=alg, **kw))
        elif op == "gather":
            for alg in _algorithms(op):
                cases.append(
                    lambda a, b, v, alg=alg, kw=kw, u=u, c=c:
                    a.gather(b(u, N), b(c, w * N), v.n, root=v.root,
                             algorithm=alg, **kw))
                cases.append(
                    lambda a, b, v, alg=alg, kw=kw, u=u:
                    a.gather(b(u, N), None, v.n, root=v.other,
                             algorithm=alg, **kw))
        elif op == "scatter":
            cases.append(lambda a, b, v, kw=kw, u=u, c=c:
                         a.scatter(b(u, w * N), b(c, N), v.n, root=v.root,
                                   **kw))
        elif op == "alltoall":
            cases.append(lambda a, b, v, kw=kw, u=u, c=c:
                         a.alltoall(b(u, w * N), b(c, w * N), v.n, **kw))
        elif op == "alltoallv":
            size = w * (w + 1) // 2
            cases.append(lambda a, b, v, kw=kw, u=u, c=c:
                         a.alltoallv(b(u, size), b(c, size, 1), v.counts,
                                     v.counts, **kw))
        elif op == "send":
            cases.append(lambda a, b, v, kw=kw, u=u:
                         a.send(b(u, N), v.n, v.peer, tag=v.tag, **kw))
        elif op == "recv":
            cases.append(lambda a, b, v, kw=kw, c=c:
                         a.recv(b(c, N), v.n, v.peer, tag=v.tag, **kw))
        elif op == "put" and bs is False:
            cases.append(lambda a, b, v, u=u, wire=wire:
                         a.put(b(u, N), v.n, v.peer, v.window,
                               offset=v.offset, notify=v.notify, comm=comm,
                               compress_dtype=wire))
        elif op == "get" and bs is False:
            cases.append(lambda a, b, v, c=c, wire=wire:
                         a.get(b(c, N), v.n, v.peer, v.window,
                               offset=v.offset, comm=comm,
                               compress_dtype=wire))
        elif op == "copy" and wire is None:
            cases.append(lambda a, b, v, u=u, c=c:
                         a.copy(b(u, N), b(c, N), v.n, comm=comm))
            cases.append(lambda a, b, v, c=c:
                         a.copy(None, b(c, N), v.n, comm=comm,
                                stream_flags=StreamFlags.OP0_STREAM))
            cases.append(lambda a, b, v, u=u:
                         a.copy(b(u, N), None, v.n, comm=comm,
                                stream_flags=StreamFlags.RES_STREAM))
            cases.append(lambda a, b, v, u=u:
                         a.copy(None, None, v.n, comm=comm, stream_dtype=u,
                                stream_flags=StreamFlags.OP0_STREAM
                                | StreamFlags.RES_STREAM))
        elif op == "combine" and wire is None:
            cases.append(lambda a, b, v, u=u, c=c:
                         a.combine(v.n, v.func, b(u, N), b(c, N),
                                   b(c, N)))
        elif op == "stream_put" and wire is None:
            cases.append(lambda a, b, v, u=u:
                         a.stream_put(b(u, N), v.n, v.peer, tag=v.tag))
    if op == "barrier":
        cases.append(lambda a, b, v: a.barrier(comm=comm))
    return cases


DIFFERENTIAL_OPS = ["nop", "barrier", "copy", "combine", "send", "recv",
                    "stream_put", "put", "get", "bcast", "scatter", "gather",
                    "reduce", "allgather", "allreduce", "reduce_scatter",
                    "alltoall", "alltoallv"]


def _issued(a):
    """Replace the driver's issue step with one that keeps each
    descriptor and retires it unexecuted."""
    seen = []

    def issue(desc, run_async, waitfor, chain=False, retries=None,
              retry_policy=None):
        seen.append(desc)
        return CompletedHandle(context=desc.scenario.name)

    a._call = issue
    return seen


def _buffers(a):
    """Two buffer sets (A and B) of each (dtype, size, slot), made once:
    a call gives two operands the same slot to alias them."""
    made = {}

    def bufs(which):
        def get(dtype, size, slot=0):
            key = (which, np.dtype(dtype).name, size, slot)
            if key not in made:
                made[key] = a.buffer((size,), np.dtype(dtype))
            return made[key]
        return get
    return bufs("A"), bufs("B")


def _same(hit: CallDescriptor, fresh: CallDescriptor):
    for f in dataclasses.fields(CallDescriptor):
        x, y = getattr(hit, f.name), getattr(fresh, f.name)
        assert x == y and type(x) is type(y), (f.name, x, y)


@pytest.fixture(scope="module", params=["emu", "sim", "tpu"])
def tier_world(request):
    accls = _world(request.param)
    for a in accls[:2]:
        a.split_communicator([0, 1, 2])
    yield accls
    _deinit(accls)


@pytest.mark.parametrize("op", DIFFERENTIAL_OPS)
def test_hit_descriptor_equals_prepare(tier_world, op):
    """The descriptor of a plan hit equals a fresh resolution's, field for
    field, for other buffers of the same signature at another count,
    root, reduce function, tag and peer."""
    checked = 0
    for a in tier_world[:2]:
        bufs_a, bufs_b = _buffers(a)
        issue = a._call
        seen = _issued(a)
        try:
            if op == "nop":
                # a nop has no signature to resolve: it is never planned
                counts = dict(a._plan_counts)
                a.nop()
                a.nop()
                assert a._plan_counts == counts
                _same(seen[-1], CallDescriptor(CCLOp.nop))
                checked += 1
                continue
            for comm in (a.comm, a.communicators[-1]):
                first, later = _Variant(0, comm), _Variant(1, comm)
                cases = _corpus(op, comm)
                # every signature of the corpus planned side by side, so
                # a key that leaves out an input gives a wrong hit
                a._plans.clear()
                refused = {}
                for i, case in enumerate(cases):
                    kept = len(a._plans)
                    try:
                        case(a, bufs_a, first)           # miss
                    except (ValueError, KeyError) as exc:
                        # a refused call keeps no plan: it is refused again
                        assert len(a._plans) == kept
                        refused[i] = type(exc)
                for i, case in enumerate(cases):
                    if i in refused:
                        with pytest.raises(refused[i]):
                            case(a, bufs_b, later)
                        continue
                    hits = a._plan_counts["hit"]
                    case(a, bufs_b, later)               # hit
                    assert a._plan_counts["hit"] == hits + 1
                    hit = seen[-1]
                    plans, a._plans = a._plans, {}
                    case(a, bufs_b, later)               # resolved afresh
                    a._plans = plans
                    _same(hit, seen[-1])
                    checked += 1
        finally:
            a._call = issue
            a._plans.clear()
    assert checked >= 2


# -- results ----------------------------------------------------------------

def _ops_pass(a, seed):
    """One pass of every collective and local op on fresh inputs; the
    results as bytes."""
    rng = np.random.default_rng(seed * 97 + a.rank)
    w = a.world_size
    out = []

    def buf(dtype, n):
        return a.buffer(data=(rng.standard_normal(n) * 4).astype(dtype))

    for dt in (np.float32, ml_dtypes.bfloat16):
        src, dst = buf(dt, N), a.buffer((N,), dt)
        a.allreduce(src, dst, N)
        out.append(dst.data)
        src, dst = buf(dt, N), a.buffer((w * N,), dt)
        a.allgather(src, dst, N)
        out.append(dst.data)
        src, dst = buf(dt, w * N), a.buffer((N,), dt)
        a.reduce_scatter(src, dst, N)
        out.append(dst.data)
    b = buf(np.float32, N)
    a.bcast(b, N, root=1)
    out.append(b.data)
    src, dst = buf(np.float32, N), a.buffer((N,), np.float32)
    a.reduce(src, dst, N, root=2)
    if a.rank == 2:
        out.append(dst.data)
    src, dst = buf(np.float32, N), a.buffer((w * N,), np.float32)
    a.gather(src, dst if a.rank == 0 else None, N, root=0)
    if a.rank == 0:
        out.append(dst.data)
    src, dst = buf(np.float32, w * N), a.buffer((N,), np.float32)
    a.scatter(src, dst, N, root=3)
    out.append(dst.data)
    src, dst = buf(np.float32, w * N), a.buffer((w * N,), np.float32)
    a.alltoall(src, dst, N)
    out.append(dst.data)
    src, dst = buf(np.float32, N), a.buffer((N,), np.float32)
    a.copy(src, dst, N)
    out.append(dst.data)
    x, y, dst = buf(np.float32, N), buf(np.float32, N), \
        a.buffer((N,), np.float32)
    a.combine(N, ReduceFunc.SUM, x, y, dst)
    out.append(dst.data)
    return [np.ascontiguousarray(r).tobytes() for r in out]


@pytest.mark.parametrize("tier", ["emu", "tpu"])
def test_hits_bit_identical_to_fresh_driver(tier):
    """A pass of planned calls gives the bytes a fresh driver gives for
    the same inputs."""
    planned = _world(tier)
    try:
        got = run_ranks(planned, lambda a: (_ops_pass(a, 0),
                                            _ops_pass(a, 1)))
        counts = [dict(a._plan_counts) for a in planned]
    finally:
        _deinit(planned)
    fresh = _world(tier)
    try:
        want = run_ranks(fresh, lambda a: _ops_pass(a, 1))
    finally:
        _deinit(fresh)
    for r in range(W):
        assert got[r][1] == want[r]
        # the second pass is all hits: each signature missed once
        assert counts[r]["bypass"] == 0
        assert counts[r]["hit"] >= counts[r]["miss"] > 0


def test_device_resident_hits_bit_identical():
    """The benchmark cells' path: device-resident bf16 operands on the
    TPU tier, one signature over rotating buffers."""
    import jax.numpy as jnp
    from accl_tpu.device.tpu import tpu_world

    def body(a, seed):
        rng = np.random.default_rng(seed * 13 + a.rank)
        out = []
        for _ in range(3):
            x = rng.standard_normal(256).astype(ml_dtypes.bfloat16)
            src = a.buffer(data=jnp.asarray(x))
            dst = a.buffer(data=jnp.zeros(256, jnp.bfloat16))
            a.allreduce(src, dst, 256)
            out.append(np.asarray(dst.jax).tobytes())
        return out

    planned = tpu_world(W)
    try:
        got = run_ranks(planned, lambda a: body(a, 5))
        assert [a._plan_counts for a in planned] == \
            [{"hit": 2, "miss": 1, "bypass": 0}] * W
    finally:
        _deinit(planned)
    fresh = tpu_world(W)
    try:
        want = run_ranks(fresh, lambda a: body(a, 5))
    finally:
        _deinit(fresh)
    assert got == want


# -- state that can change between two calls of one signature --------------

def _allreduce(a, **kw):
    src = a.buffer(data=np.arange(N, dtype=np.float32) + a.rank)
    dst = a.buffer((N,), np.float32)
    a.allreduce(src, dst, N, **kw)
    return dst.data.copy()


def _counts_after(accls, fn):
    before = [dict(a._plan_counts) for a in accls]
    run_ranks(accls, fn)
    return [{k: a._plan_counts[k] - b[k] for k in b}
            for a, b in zip(accls, before)]


def test_bypass_tuner_auto_and_explicit_algorithm():
    t = Tuner(topology=EMU_TOPO)
    accls = emu_world(2, tuner=t)
    try:
        d = _counts_after(accls, lambda a: [_allreduce(a) for _ in range(3)])
        assert d == [{"hit": 0, "miss": 0, "bypass": 3}] * 2
        d = _counts_after(accls, lambda a: [
            _allreduce(a, algorithm=CollectiveAlgorithm.RING)
            for _ in range(3)])
        assert d == [{"hit": 2, "miss": 1, "bypass": 0}] * 2
        # a tuner's block size is tuner state too
        d = _counts_after(accls, lambda a: _allreduce(
            a, algorithm="ring", compress_dtype=ml_dtypes.float8_e4m3fn,
            block_scale=True))
        assert d == [{"hit": 0, "miss": 0, "bypass": 1}] * 2
        # an AUTO plan kept while no tuner was attached yields to one
        # attached later
        for a in accls:
            a.tuner = None
        d = _counts_after(accls, lambda a: [_allreduce(a) for _ in range(2)])
        assert d == [{"hit": 1, "miss": 1, "bypass": 0}] * 2
        for a in accls:
            a.tuner = t
        d = _counts_after(accls, _allreduce)
        assert d == [{"hit": 0, "miss": 0, "bypass": 1}] * 2
    finally:
        _deinit(accls)


def test_bypass_auto_wire():
    """"auto" is resolved afresh on every call, before the plan, and the
    plan is keyed on its answer."""
    accls = emu_world(2)
    try:
        # no tuner: "auto" stays uncompressed, the plain signature's plan
        d = _counts_after(accls, lambda a: [
            _allreduce(a), _allreduce(a, compress_dtype="auto")])
        assert d == [{"hit": 1, "miss": 1, "bypass": 0}] * 2
    finally:
        _deinit(accls)
    t = Tuner(topology=EMU_TOPO)
    small, big = 256, 1 << 18
    # the tuner's answer, pinned: quantize the large call only
    t.select_wire = lambda op, world, nbytes, ratio=None: nbytes >= big
    accls = emu_world(2, tuner=t)
    a = accls[0]
    try:
        seen = _issued(a)
        src = a.buffer((big,), np.float32)
        dst = a.buffer((big,), np.float32)

        def call(n):
            a.allreduce(src, dst, n, algorithm="ring",
                        compress_dtype="auto", block_scale=64)
        for n in (small, big, small, big):
            call(n)
        assert [bool(d.compression & Compression.BLOCK_SCALED)
                for d in seen] == [False, True, False, True]
        assert a._plan_counts == {"hit": 2, "miss": 2, "bypass": 0}
        a._plans.clear()
        call(small)
        call(big)
        _same(seen[2], seen[4])
        _same(seen[3], seen[5])
    finally:
        _deinit(accls)


def test_bypass_hierarchy():
    """The hierarchical route is decided afresh on every call, before the
    plan: a flat signature keeps its plan, and HIERARCHICAL still lowers
    to the hierarchy's phase program."""
    hosts = [0, 0, 1, 1]
    accls = emu_world(4, hosts=hosts, nbufs=32)
    try:
        flat = run_ranks(accls, lambda a: _allreduce(a, algorithm="ring"))
        lowered = []
        for a in accls:
            a.configure_hierarchy(hosts)
            run = a._hier.run
            a._hier.run = (lambda op, _run=run, **kw:
                           (lowered.append(op), _run(op, **kw))[1])
        d = _counts_after(accls, lambda a: _allreduce(a, algorithm="ring"))
        assert d == [{"hit": 1, "miss": 0, "bypass": 0}] * 4
        assert lowered == []
        got = run_ranks(accls, lambda a: _allreduce(a, algorithm=H))
        assert lowered == ["allreduce"] * 4
        for r in range(4):
            np.testing.assert_array_equal(got[r], flat[r])
    finally:
        _deinit(accls)


def test_bypass_verify_integrity():
    """Verification is decided afresh on every call: a planned call with
    verification on still verifies its result."""
    accls = emu_world(2)
    try:
        run_ranks(accls, _allreduce)
        verified = []
        for a in accls:
            orig = a._verify_result
            a._verify_result = (lambda *args, _o=orig:
                                (verified.append(args[0]), _o(*args)))
        d = _counts_after(accls, lambda a: _allreduce(
            a, verify_integrity=True))
        # the fingerprint exchange is an allgather of its own signature
        assert d == [{"hit": 1, "miss": 1, "bypass": 0}] * 2
        assert verified == ["allreduce", "allreduce"]
        for a in accls:
            a.verify_integrity = True
        d = _counts_after(accls, _allreduce)
        assert d == [{"hit": 2, "miss": 0, "bypass": 0}] * 2
        assert len(verified) == 4
    finally:
        _deinit(accls)


def test_bypass_inside_a_phase():
    """A phase of a logical call uses the signature's plan, and its record
    still names the logical call."""
    accls = emu_world(2)
    try:
        run_ranks(accls, _allreduce)
        for a in accls:
            a.start_profiling()

        def phase(a):
            with a._attributed("logical"):
                return _allreduce(a)
        d = _counts_after(accls, phase)
        assert d == [{"hit": 1, "miss": 0, "bypass": 0}] * 2
        for a in accls:
            a.end_profiling()
            recs = [r for r in a.profiler.records if r.op == "allreduce"]
            assert [r.parent for r in recs] == ["logical"]
    finally:
        _deinit(accls)


def test_bypass_revoked_communicator_still_peer_failed():
    accls = emu_world(2)
    try:
        run_ranks(accls, lambda a: [_allreduce(a) for _ in range(2)])
        a = accls[0]
        a.comm.revoke()
        counts = dict(a._plan_counts)
        with pytest.raises(ACCLError) as ei:
            _allreduce(a)
        assert ei.value.error_word & int(ErrorCode.PEER_FAILED)
        # refused before the plan is looked up
        assert a._plan_counts == counts
    finally:
        _deinit(accls)


def test_registry_change_resolves_again():
    accls = emu_world(1)
    a = accls[0]
    try:
        src = a.buffer(data=np.ones(N, np.float32))
        dst = a.buffer((N,), np.float32)
        a.copy(src, dst, N)
        a.copy(src, dst, N)
        assert a._plan_counts == {"hit": 1, "miss": 1, "bypass": 0}
        key = ("float32", "float32")
        mine = ArithConfig(np.dtype("float32"), np.dtype("float32"),
                           supported_funcs=(ReduceFunc.SUM,))
        # an in-place change, announced
        a.arith_registry[key] = mine
        a.invalidate_arith_cache()
        a.copy(src, dst, N)
        assert a._plan_counts["miss"] == 2
        assert next(iter(a._plans.values())).arithcfg is mine
        # rebinding the registry drops the plans as well
        a.arith_registry = dict(DEFAULT_ARITH_CONFIGS)
        assert not a._plans
        a.copy(src, dst, N)
        assert a._plan_counts["miss"] == 3
        assert next(iter(a._plans.values())).arithcfg is not mine
        np.testing.assert_array_equal(dst.data, src.data)
    finally:
        _deinit(accls)


# -- counting ---------------------------------------------------------------

def test_every_kind_of_call_uses_the_plan():
    """Synchronous, asynchronous, dependent, chained and retried calls all
    build their descriptor from the plan and retire through the one call
    path."""
    accls = emu_world(1)
    a = accls[0]
    try:
        src = a.buffer(data=np.arange(N, dtype=np.float32))
        dst = a.buffer((N,), np.float32)
        issued = []
        once = a._call_once
        a._call_once = lambda *args: (issued.append(args[0]),
                                      once(*args))[1]
        a.copy(src, dst, N)
        a.copy(src, dst, N, retries=0)
        a.copy(src, dst, N, run_async=True).wait(10)
        a.copy(src, dst, N, waitfor=[CompletedHandle()])
        a.copy(src, dst, N, chain=True)
        a.copy(src, dst, N, run_async=True, chain=True).wait(10)
        a.copy(src, dst, N, retries=2)
        assert [d.scenario for d in issued] == [CCLOp.copy] * 7
        assert a._plan_counts == {"hit": 6, "miss": 1, "bypass": 0}
        np.testing.assert_array_equal(dst.data, src.data)
    finally:
        _deinit(accls)


def test_plan_cap_drops_the_oldest(monkeypatch):
    monkeypatch.setattr(accl_mod, "_MAX_CALL_PLANS", 2)
    accls = emu_world(1)
    a = accls[0]
    try:
        for dt in (np.float32, np.float64, np.int32):
            a.copy(a.buffer(data=np.ones(N, dt)), a.buffer((N,), dt), N)
        assert [k[2] for k in a._plans] == [np.dtype(np.float64),
                                            np.dtype(np.int32)]
        a.copy(a.buffer(data=np.ones(N, np.float32)),
               a.buffer((N,), np.float32), N)
        assert a._plan_counts == {"hit": 0, "miss": 4, "bypass": 0}
        assert len(a._plans) == 2
    finally:
        _deinit(accls)


def test_counter_counts_hit_miss_and_bypass():
    accls = emu_world(2, tuner=Tuner(topology=EMU_TOPO))
    try:
        run_ranks(accls, lambda a: [_allreduce(a, algorithm="ring")
                                    for _ in range(3)] + [_allreduce(a)])
        ctx = accls[0].device.ctx.fabric.ctx_seq
        rows = METRICS.snapshot()["counters"]["accl_call_plan_total"]
        for a in accls:
            mine = {k.rsplit("result=", 1)[1]: v for k, v in rows.items()
                    if f"rank={a.rank}," in k and f"ctx={ctx}," in k}
            assert mine == {"hit": 2, "miss": 1, "bypass": 1}
    finally:
        _deinit(accls)


# -- planned calls: errors, timeouts, records, observations -----------------

def test_planned_retire_raises_the_call_error():
    from accl_tpu.device.tpu import tpu_world
    accls = tpu_world(1)
    a = accls[0]
    try:
        src = a.buffer(data=np.ones(N, np.float32))
        dst = a.buffer((N,), np.float32)
        a.allreduce(src, dst, N)
        for _ in range(2):
            with pytest.raises(ACCLError) as ei:
                a.allreduce(src, dst, 2 * N)      # past the buffers' end
            assert ei.value.error_word & int(ErrorCode.INVALID_CALL)
        assert a._plan_counts == {"hit": 2, "miss": 1, "bypass": 0}
        errs = METRICS.snapshot()["counters"]["accl_call_errors_total"]
        assert sum(v for k, v in errs.items() if "op=allreduce" in k) >= 2
    finally:
        _deinit(accls)


def test_planned_retire_times_out():
    accls = emu_world(2, timeout=0.3)
    try:
        def pair(a):
            if a.rank == 0:
                a.send(a.buffer(data=np.ones(N, np.float32)), N, 1, tag=4)
            else:
                a.recv(a.buffer((N,), np.float32), N, 0, tag=4)
        run_ranks(accls, pair)
        b = accls[1]
        hits = b._plan_counts["hit"]
        with pytest.raises(ACCLError) as ei:
            b.recv(b.buffer((N,), np.float32), N, 0, tag=4)
        assert ei.value.error_word & int(ErrorCode.RECEIVE_TIMEOUT_ERROR)
        assert b._plan_counts["hit"] == hits + 1
    finally:
        _deinit(accls)


def _record_fields(r):
    d = dataclasses.asdict(r)
    for k in ("t_start", "duration_s", "expand_us", "plan_us",
              "overlap_frac", "pipelined_moves", "pipeline_depth",
              "combine_overlap", "lanes", "moves", "plan_cache"):
        d.pop(k)
    return d


@pytest.mark.parametrize("tier", ["emu", "tpu"])
def test_profiler_records_match_the_full_path(tier):
    accls = _world(tier, 2)
    try:
        for a in accls:
            a.start_profiling()

        def body(a):
            _allreduce(a)                      # miss
            _allreduce(a)                      # hit
            _allreduce(a, chain=True)          # hit, chained
            src = a.buffer(data=np.ones(N, np.float32))
            a.copy(src, a.buffer((N,), np.float32), N)
            a.copy(src, a.buffer((N,), np.float32), N, chain=True)
            a.nop()
            a.nop(chain=True)
        run_ranks(accls, body)
        for a in accls:
            a.end_profiling()
            recs = [_record_fields(r) for r in a.profiler.records
                    if r.op != "config"]
            assert [r["op"] for r in recs] == ["allreduce"] * 3 + \
                ["copy"] * 2 + ["nop"] * 2
            assert recs[0] == recs[1] == recs[2]
            assert recs[3] == recs[4]
            assert recs[5] == recs[6]
            assert recs[0]["nbytes"] == N * 4 and recs[0]["algorithm"]
    finally:
        _deinit(accls)


def test_tuner_observes_planned_calls():
    t = Tuner(topology=EMU_TOPO)
    seen = []
    observe = t.observe
    t.observe = lambda *args, **kw: (seen.append(args[:4]),
                                     observe(*args, **kw))[1]
    accls = emu_world(2, tuner=t)
    try:
        d = _counts_after(accls, lambda a: [
            _allreduce(a, algorithm=CollectiveAlgorithm.RING)
            for _ in range(3)])
        assert d == [{"hit": 2, "miss": 1, "bypass": 0}] * 2
        assert seen == [("allreduce", 2, N * 4,
                         CollectiveAlgorithm.RING)] * 6
    finally:
        _deinit(accls)


def test_block_scale_true_and_one_are_two_signatures():
    """``block_scale=True`` asks for the default block and ``1`` names a
    block of one: equal as dict keys, so the key tells them apart."""
    accls = emu_world(2)
    a = accls[0]
    try:
        seen = _issued(a)
        src = a.buffer((N,), np.float32)
        dst = a.buffer((N,), np.float32)
        for bs in (True, 1, True, 1):
            a.allreduce(src, dst, N, compress_dtype=ml_dtypes.float8_e4m3fn,
                        block_scale=bs)
        blocks = [d.arithcfg.quant_block for d in seen]
        assert blocks[0] == blocks[2] != blocks[1] == blocks[3]
        assert a._plan_counts == {"hit": 2, "miss": 2, "bypass": 0}
    finally:
        _deinit(accls)
