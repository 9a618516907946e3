"""Tests for the tracing/profiling subsystem (accl_tpu/tracing.py).

Parity targets: nop call-latency probe (reference accl.py:738-745, warmup at
test.py:934-936), start/end_profiling config calls (xlnx-consts.hpp:27-28),
CSV record dumps in the benchmark harness's shape (test.py:949).
"""

import collections
import csv
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from accl_tpu import tracing
from accl_tpu.constants import ReduceFunc
from accl_tpu.testing import emu_world, run_ranks
from accl_tpu.tracing import CallRecord, Profiler


def test_profiler_records_and_summary():
    p = Profiler()
    p.start()
    for i in range(10):
        p.record(CallRecord(op="allreduce", count=256, nbytes=1024,
                            comm_id=0, t_start=float(i),
                            duration_s=1e-3 * (i + 1)))
    p.record(CallRecord(op="send", count=1, nbytes=4, comm_id=0,
                        t_start=0.0, duration_s=5e-4))
    s = p.summary()
    assert set(s) == {"allreduce", "send"}
    ar = s["allreduce"]
    assert ar.n == 10
    assert ar.min_us == pytest.approx(1000.0)
    assert ar.max_us == pytest.approx(10000.0)
    assert ar.p50_us == pytest.approx(6000.0, rel=0.2)
    assert ar.total_bytes == 10240
    assert ar.mean_gbps > 0
    assert "allreduce" in p.table()


def test_profiler_csv(tmp_path):
    p = Profiler()
    p.start()  # record() honors the armed flag
    p.record(CallRecord(op="bcast", count=8, nbytes=32, comm_id=3,
                        t_start=1.25, duration_s=2e-6, error_word=0))
    path = tmp_path / "prof.csv"
    p.to_csv(str(path))
    rows = list(csv.DictReader(open(path)))
    assert len(rows) == 1
    assert rows[0]["op"] == "bcast"
    assert int(rows[0]["nbytes"]) == 32
    assert float(rows[0]["duration_us"]) == pytest.approx(2.0)


def test_driver_profiling_end_to_end():
    """start_profiling arms capture through the real call path; records
    carry op names, element counts and payload bytes; end_profiling
    disarms."""
    accls = run_ranks(emu_world(2), _profiled_allreduce)
    for recs in accls:
        ops = [r.op for r in recs]
        assert ops.count("allreduce") == 3
        assert all(r.nbytes == 64 * 4 for r in recs if r.op == "allreduce")
        assert all(r.error_word == 0 for r in recs)
        assert all(r.duration_s >= 0 for r in recs)


def _profiled_allreduce(a):
    src = a.buffer(data=np.arange(64, dtype=np.float32))
    dst = a.buffer((64,), np.float32)
    a.allreduce(src, dst, 64)          # before arming: not recorded
    a.start_profiling()
    for _ in range(3):
        a.allreduce(src, dst, 64)
    a.end_profiling()
    a.allreduce(src, dst, 64)          # after disarm: not recorded
    return a.profiler.records


def test_async_chain_attribution():
    """Async chained calls are recorded at retire time with their true
    durations (done-callback path), not at dispatch."""
    def body(a):
        src = a.buffer(data=np.ones(32, np.float32))
        dst = a.buffer((32,), np.float32)
        a.start_profiling()
        h1 = a.allreduce(src, dst, 32, run_async=True)
        h2 = a.allreduce(dst, src, 32, run_async=True, waitfor=[h1])
        h2.wait()
        a.end_profiling()
        # both retired -> both recorded even though issued async
        assert len(a.profiler.records) == 2
        return True

    assert all(run_ranks(emu_world(2), body))


def test_profiler_csv_roundtrip():
    """Records survive export/import byte-faithfully enough to re-feed
    analysis (and a Tuner): every field including the algorithm label."""
    p = Profiler()
    p.start()
    p.record(CallRecord(op="allreduce", count=256, nbytes=1024, comm_id=2,
                        t_start=1.5, duration_s=3.25e-4,
                        algorithm="FUSED_RING", lanes=4,
                        overlap_frac=0.75))
    p.record(CallRecord(op="send", count=8, nbytes=32, comm_id=0,
                        t_start=2.0, duration_s=1e-5, error_word=4))
    path_ = "prof_rt.csv"

    def roundtrip(tmp):
        p.to_csv(tmp)
        return Profiler.read_csv(tmp)

    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        back = roundtrip(os.path.join(d, path_))
    assert len(back) == 2
    a, s = back
    assert (a.op, a.count, a.nbytes, a.comm_id) == ("allreduce", 256,
                                                    1024, 2)
    assert a.algorithm == "FUSED_RING"
    assert a.duration_s == pytest.approx(3.25e-4, rel=1e-6)
    assert (a.lanes, a.overlap_frac) == (4, pytest.approx(0.75))
    assert s.error_word == 4 and s.algorithm == ""
    assert (s.lanes, s.overlap_frac) == (0, 0.0)
    # re-imported records aggregate identically
    p2 = Profiler()
    p2.start()
    for r in back:
        p2.record(r)
    assert p2.summary()["allreduce"].total_bytes == 1024


def test_percentile_math_known_inputs():
    """p50/p95 on known inputs (nearest-rank on the sorted sample)."""
    vals = sorted(float(v) for v in range(1, 101))  # 1..100
    assert tracing._percentile(vals, 0.50) == 51.0  # idx round(49.5)=50
    assert tracing._percentile(vals, 0.95) == 95.0  # idx round(94.05)=94
    assert tracing._percentile(vals, 0.0) == 1.0
    assert tracing._percentile(vals, 1.0) == 100.0
    assert tracing._percentile([], 0.5) == 0.0
    p = Profiler()
    p.start()
    for v in vals:
        p.record(CallRecord(op="nop", count=0, nbytes=0, comm_id=0,
                            t_start=0.0, duration_s=v * 1e-6))
    s = p.summary()["nop"]
    assert s.p50_us == pytest.approx(51.0)
    assert s.p95_us == pytest.approx(95.0)
    assert s.mean_us == pytest.approx(50.5)


def test_nop_latency_probe():
    accls = emu_world(1)
    stats = tracing.measure_call_latency(accls[0], n=20)
    assert stats["p50_us"] > 0
    assert stats["min_us"] <= stats["p50_us"] <= stats["p95_us"]
    assert stats["n"] == 20.0
    assert stats["mean_us"] >= stats["min_us"]


def _trace_events(log_dir, prefix="accl."):
    """``(name, line, start_ns, end_ns, stats)`` of the host events named
    ``prefix*`` in the one xplane trace under ``log_dir``; ``line`` tells
    threads apart."""
    from jax.profiler import ProfileData
    path = next(Path(log_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(prefix):
                    out.append((e.name, i, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return out


def _parents(events):
    """Each event's enclosing event on its own line (None at the root)."""
    parent = {}
    by_line = collections.defaultdict(list)
    for ev in events:
        by_line[ev[1]].append(ev)
    for evs in by_line.values():
        stack = []
        for ev in sorted(evs, key=lambda e: (e[2], -e[3])):
            while stack and stack[-1][3] <= ev[2]:
                stack.pop()
            parent[id(ev)] = stack[-1] if stack else None
            stack.append(ev)
    return parent


@pytest.fixture
def spans_off():
    """Whatever an earlier test left armed, spans start and end off."""
    while tracing.SPANS.enabled:
        tracing.SPANS.disarm()
    yield tracing.SPANS
    while tracing.SPANS.enabled:
        tracing.SPANS.disarm()


def test_annotate_and_trace_smoke(tmp_path, spans_off):
    """annotate() is a no-op while disarmed; armed, it writes a
    TraceAnnotation whose args read back as the event's stats, and keeps
    the span in SPANS."""
    import jax
    import jax.numpy as jnp

    before = spans_off.spans()
    with tracing.annotate("unit-test-region", call=1) as span:
        span.set(group="0:0")
    assert spans_off.spans() == before
    spans_off.arm()
    try:
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            with tracing.annotate("accl.unit", call=7, nbytes=32) as span:
                x = jnp.ones((8,)) + 1
                span.set(group="3:1")
        finally:
            jax.profiler.stop_trace()
    finally:
        spans_off.disarm()
    assert float(x[0]) == 2.0
    (ev,) = _trace_events(tmp_path / "trace")
    assert ev[0] == "accl.unit" and ev[3] > ev[2]
    assert ev[4] == {"call": 7, "nbytes": 32, "group": "3:1"}
    (kept,) = spans_off.spans()
    assert kept[0] == "accl.unit" and kept[4] == ev[4]
    assert kept[1] == threading.get_ident() and kept[3] > kept[2]


def _tpu_calls(a, n=64):
    """One allreduce on device-resident buffers (every rank; rank 0 comes
    last, so the others wait for it), then on rank 0 a copy, a combine
    and a nop on device-resident buffers."""
    import jax
    import jax.numpy as jnp
    dev = a.device.my_device
    src = a.buffer(data=jax.device_put(jnp.arange(n, dtype=jnp.float32),
                                       dev))
    dst = a.buffer(data=jax.device_put(jnp.zeros(n, jnp.float32), dev))
    if a.rank == 0:
        time.sleep(0.2)
    a.allreduce(src, dst, n)
    if a.rank == 0:
        a.copy(src, dst, n)
        a.combine(n, ReduceFunc.SUM, src, dst, dst)
        a.nop()
    return np.asarray(dst.jax)


class _Built(Exception):
    pass


def _refuse(*args, **kwargs):
    raise _Built(args)


def test_spans_off_build_nothing(spans_off, monkeypatch):
    """With profiling off, no call path builds a span: an allreduce, copy,
    combine and nop on the TPU tier construct no TraceAnnotation."""
    from accl_tpu.device.tpu import tpu_world
    monkeypatch.setattr(tracing, "_Span", _refuse)
    monkeypatch.setattr(spans_off, "annotation", _refuse)
    before = spans_off.spans()
    accls = tpu_world(4, platform="cpu")
    try:
        out = run_ranks(accls, _tpu_calls)
    finally:
        for a in accls:
            a.deinit()
    assert spans_off.spans() == before
    # rank 0 ends on its combine (src + a copy of src), the others on
    # the four-rank sum
    assert out[0][:3].tolist() == [0.0, 2.0, 4.0]
    assert out[1][:3].tolist() == [0.0, 4.0, 8.0]


def test_tpu_tier_spans_nest_on_the_trace(tmp_path, spans_off):
    """With profiling on, a CPU profiler trace holds accl.call.* ->
    accl.deposit / accl.wait / accl.launch.* -> accl.assemble /
    accl.dispatch / accl.rebind on the right threads, sharing ``call`` and
    ``group``; copy and combine carry accl.stage.* with their bytes."""
    import jax
    from accl_tpu.device.tpu import tpu_world
    n = 64
    accls = tpu_world(4, platform="cpu")
    try:
        run_ranks(accls, _tpu_calls)           # warm: programs built
        for a in accls:
            a.start_profiling()
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            run_ranks(accls, _tpu_calls)
        finally:
            jax.profiler.stop_trace()
            for a in accls:
                a.end_profiling()
    finally:
        for a in accls:
            a.deinit()
    assert not spans_off.enabled and spans_off.lowerings == 0
    evs = [e for e in _trace_events(tmp_path / "trace")
           if e[0] != "accl.call.config"]
    parent = _parents(evs)
    names = collections.Counter(e[0] for e in evs)
    # a rank whose allreduce retired before it came to wait records none
    assert 1 <= names.pop("accl.wait", 0) <= 3
    assert names == {
        "accl.call.allreduce": 4, "accl.deposit": 4,
        "accl.launch.allreduce": 1, "accl.assemble": 1, "accl.dispatch": 1,
        "accl.rebind": 1, "accl.call.copy": 1, "accl.call.combine": 1,
        "accl.call.nop": 1, "accl.stage.read": 3, "accl.stage.write": 2,
        "accl.stage.reduce": 1}
    roots = [e for e in evs if e[0].startswith("accl.call.")]
    assert all(parent[id(e)] is None for e in roots)
    assert len({e[4]["call"] for e in roots}) == len(roots)
    calls = {e[4]["call"]: e for e in roots}
    for e in evs:
        if e in roots:
            continue
        p = parent[id(e)]
        if e[0] in ("accl.assemble", "accl.dispatch", "accl.rebind"):
            assert p[0] == "accl.launch.allreduce"
        else:
            assert p in roots, e
        assert e[4]["call"] == p[4]["call"] and e[1] == p[1]
    ar = [e for e in evs if e[0] in ("accl.deposit", "accl.wait",
                                     "accl.launch.allreduce")]
    assert len({e[4]["group"] for e in ar}) == 1
    launch = next(e for e in evs if e[0] == "accl.launch.allreduce")
    assert launch[4]["nbytes"] == n * 4
    waits = [e[1] for e in evs if e[0] == "accl.wait"]
    assert launch[1] not in waits and len(set(waits)) == len(waits)
    for op, reads in (("copy", 1), ("combine", 2)):
        root = next(e for e in roots if e[0] == f"accl.call.{op}")
        assert root[4] == {"call": root[4]["call"], "op": op,
                           "nbytes": n * 4, "comm": roots[0][4]["comm"]}
        stage = [e for e in evs if e[0].startswith("accl.stage.")
                 and e[4]["call"] == root[4]["call"]]
        got = sorted((e[0], e[4].get("nbytes")) for e in stage)
        want = ([("accl.stage.read", n * 4)] * reads
                + ([("accl.stage.reduce", None)] if op == "combine" else [])
                + [("accl.stage.write", n * 4)])
        assert got == sorted(want, key=lambda t: t[0])
    kept = collections.Counter(s[0] for s in spans_off.spans()
                               if s[0] != "accl.call.config")
    assert kept == names + collections.Counter({"accl.wait": len(waits)})


def test_spans_past_capacity_are_counted(spans_off, monkeypatch):
    """Past its capacity the log keeps the interval's first spans and
    counts the rest; the next arming starts both afresh."""
    monkeypatch.setattr(tracing, "SPAN_CAPACITY", 2)
    for _ in range(2):
        spans_off.arm()
        try:
            for call in (1, 2, 3):
                with tracing.annotate("accl.unit", call=call):
                    pass
        finally:
            spans_off.disarm()
        assert [s[4]["call"] for s in spans_off.spans()] == [1, 2]
        assert spans_off.dropped == 1


def test_a_span_holds_its_own_cost(spans_off):
    """A kept span starts before its annotation is built and ends after it
    has closed: a child's entry lies inside its parent's, and the
    parent's self time leaves the child's making out."""
    spans_off.arm()
    try:
        with tracing.annotate("accl.parent", call=1):
            with tracing.annotate("accl.child", call=1, nbytes=8):
                pass
    finally:
        spans_off.disarm()
    child, parent = spans_off.spans()
    assert (child[0], parent[0]) == ("accl.child", "accl.parent")
    assert parent[2] <= child[2] < child[3] <= parent[3]


@pytest.mark.parametrize("func,algorithm", [("SUM", "xla"),
                                            ("PROD", "ring")])
def test_programs_named_after_the_algorithm_they_run(func, algorithm):
    """A program's module is named after the algorithm it runs: XLA has
    no product all-reduce, so a PROD allreduce asked of XLA is the ring."""
    import jax
    import jax.numpy as jnp
    from accl_tpu.parallel.collectives import MeshCollectives
    from accl_tpu.parallel.mesh import make_mesh
    coll = MeshCollectives(make_mesh((4,), ("rank",), platform="cpu"),
                           "rank")
    prog = coll._program_flat("allreduce", "xla", ReduceFunc[func], None,
                              None)
    text = prog.lower(jax.ShapeDtypeStruct((4 * 8,), jnp.float32)).as_text()
    assert f"jit_accl_allreduce_{algorithm}" in text


def test_lowerings_counted_on_a_new_shape_only():
    """jax_lowerings_total rises when a jitted function meets a new shape
    and not when it meets one again."""
    import jax
    import jax.numpy as jnp
    tracing.watch_lowerings()
    tracing.watch_lowerings()            # idempotent: one listener
    f = jax.jit(lambda x: x * 3 + 1)
    x5, x6 = jnp.ones(5), jnp.ones(6)

    def count():
        return tracing.METRICS.counter("jax_lowerings_total")

    n0 = count()
    f(x5).block_until_ready()
    n1 = count()
    f(x5).block_until_ready()
    n2 = count()
    f(x6).block_until_ready()
    n3 = count()
    assert n1 == n0 + 1 and n2 == n1 and n3 == n2 + 1


def test_span_arming_is_counted_across_threads(spans_off):
    """Many threads arm, emit and disarm at once: call ids never repeat,
    and spans end off once every holder has let go."""
    import sys
    ids, errors = [], []

    def worker():
        try:
            for _ in range(200):
                spans_off.arm()
                call = spans_off.next_call()
                with tracing.annotate("accl.stress", call=call):
                    ids.append(call)
                spans_off.disarm()
        except Exception as exc:  # noqa: BLE001 — reported below
            errors.append(exc)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads) and not errors
    assert len(ids) == len(set(ids)) == 16 * 200
    assert not spans_off.enabled and spans_off.lowerings is not None
