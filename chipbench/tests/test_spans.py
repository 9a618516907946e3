"""The span metrics: the program's ``accl.*`` spans read per cell, on a CPU
rehearsal of a profiled window and on traces recorded on the chip
(``record_spans.py``). Nothing here is a device measurement."""

from __future__ import annotations

import bisect
import collections
import dataclasses
import types
from pathlib import Path

import pytest

from accl_tpu import tracing
from accl_tpu.device.tpu import tpu_world

from chipbench import drive, peaks, run, spans, spec
from chipbench.trace_reduce import reduce_trace

HERE = Path(__file__).resolve().parent
DATA = HERE / "data"
SEED = 2**33 + 29
TINY = {"ddp25.f32": {"parameters": 3000, "bucket_cap_mb": 0.004},
        "acclbench.local": {"sizes_bytes": [8, 800, 8000]}}
NEW = {"ddp25.f32": ("launch_us.bucket", "wait_us.bucket",
                     "call_self_us.bucket", "lowerings.bucket"),
       "acclbench.local": ("stage_us.small", "launch_us.small",
                           "call_self_us.small", "lowerings.small")}


def tiny(name: str) -> spec.Cell:
    cell = spec.find_cell(name)
    return dataclasses.replace(cell, config=dict(cell.config, **TINY[name]))


def read_all(cell: spec.Cell, reading) -> dict:
    return {name: spec.reader(name)(reading) for name in NEW[cell.name]}


@pytest.mark.parametrize("name", sorted(NEW))
def test_span_readers_read_a_profiled_window(name):
    """A profiled window, as a traced run profiles it: every span metric
    reads a number, and a warmed-up window lowers nothing."""
    cell = tiny(name)
    accls = tpu_world(cell.chips)
    try:
        r = drive.Run(accls, cell, SEED)
        r.warm()
        for a in accls:
            a.start_profiling()
        try:
            win = r.run(seconds=0.3)
        finally:
            for a in accls:
                a.end_profiling()
        r.results()
    finally:
        for a in accls:
            a.deinit()
    got = read_all(cell, run.Reading(cell, 1.0, win, {}, None, None))
    assert all(v is not None for v in got.values()), got
    assert got[NEW[name][-1]] == 0
    assert all(v > 0 for k, v in got.items() if not k.startswith("lower"))
    s = spans.from_program()
    # each allreduce is launched once, on one rank's thread; in ddp25 the
    # other three ranks may wait for it (a rank whose call has retired by
    # the time it would wait records none)
    launches = s.named("accl.launch.")
    assert len(launches) == sum(c for (op, _), c in win.issued.items()
                                if op == "allreduce")
    waits = s.named("accl.wait")
    if name == "ddp25.f32":
        assert 0 < len(waits) <= 3 * len(launches)
        launched = {x[4]["group"]: x[1] for x in launches}
        assert all(launched[w[4]["group"]] != w[1] for w in waits)
    else:
        assert waits == []


def test_span_readers_read_nothing_from_a_program_without_spans(monkeypatch):
    """A program that keeps no spans (as before they existed) gives no
    reading and raises nothing."""
    monkeypatch.delattr(tracing, "SPANS")
    for name in NEW:
        cell = spec.find_cell(name)
        reading = run.Reading(cell, 1.0, None, {}, None, None)
        assert read_all(cell, reading) == {m: None for m in NEW[name]}


def test_a_reading_fails_on_dropped_spans(monkeypatch):
    """Spans the program dropped past its capacity fail the reading rather
    than leave it to a part of the window."""
    monkeypatch.setattr(tracing, "SPANS", types.SimpleNamespace(
        spans=lambda: [], dropped=3, lowerings=0))
    with pytest.raises(ValueError, match="dropped 3 spans"):
        spans.from_program()


def test_self_time_and_per_call_sums_on_synthetic_spans():
    s = spans.Spans([
        ("accl.call.copy", 1, 0, 10_000, {"call": 1}),
        ("accl.stage.read", 1, 1_000, 3_000, {"call": 1}),
        ("accl.stage.write", 1, 5_000, 9_000, {"call": 1}),
        ("accl.call.allreduce", 2, 0, 8_000, {"call": 2}),
        ("accl.launch.allreduce", 2, 2_000, 7_000, {"call": 2}),
        ("accl.dispatch", 2, 3_000, 6_000, {"call": 2}),
        ("chipbench.call.copy", 1, 0, 20_000, {}),
    ])
    assert sorted(s.self_us("accl.call.")) == [3.0, 4.0]
    assert s.median_self_us("accl.call.") == 3.5
    assert s.per_call_us("accl.stage.") == {1: 6.0}
    assert s.median_per_call_us("accl.stage.") == 6.0
    assert s.median_us("accl.launch.") == 5.0
    assert s.median_us("accl.wait") is None
    assert spans.Spans([]).median_self_us("accl.call.") is None


# what the accepted readers read from the traces recorded before the
# program had spans: (chips, cell, idle share, coll_roofline of one step)
BEFORE = {"ddp4.xplane.pb": ([0, 1, 2, 3], "ddp25.f32", 68.9568834524948,
                             42.42313105416032),
          "local.xplane.pb": ([0], "acclbench.local", 99.9555041279005,
                              None)}
ONE_STEP = collections.Counter({("allreduce", 6_553_600): 51,
                                ("allreduce", 5_766_400): 1})


@pytest.mark.parametrize("trace", sorted(BEFORE))
def test_existing_metrics_read_as_before(trace):
    """The accepted device-trace metrics read, to the last bit, what they
    read before the program's spans existed."""
    chips, name, idle, roofline = BEFORE[trace]
    cell = spec.find_cell(name)
    win = drive.Window(1, 0.05, [0], [0.05], 52, 0, ONE_STEP, 4)
    reading = run.Reading(cell, 1.0, win, peaks.peak_for("TPU v5 lite"),
                          None, reduce_trace(str(DATA / trace), chips))
    idle_metric = "idle_share." + ("bucket" if cell.chips > 1 else "small")
    assert spec.reader(idle_metric)(reading) == idle
    assert spec.reader("coll_roofline")(reading) == roofline


# traces recorded on a TPU v5 lite host by record_spans.py: chips, and
# what the span metrics read from them
RECORDED = {
    "ddp4.spans.xplane.pb": ([0, 1, 2, 3], {
        "launch_us.bucket": 866.8745, "wait_us.bucket": 1489.3605,
        "call_self_us.bucket": 41.175}),
    "local.spans.xplane.pb": ([0], {
        "stage_us.small": 338.38550000000004, "launch_us.small": 370.92,
        "call_self_us.small": 57.9})}


@pytest.mark.parametrize("trace", sorted(RECORDED))
def test_span_metrics_on_recorded_chip_traces(trace, monkeypatch):
    """Each span metric reads its spans as a traced run's readers see them,
    here standing in for the program's in-memory copy."""
    chips, want = RECORDED[trace]
    recorded = spans.Spans.from_trace(str(DATA / trace))
    monkeypatch.setattr(tracing, "SPANS", types.SimpleNamespace(
        spans=lambda: recorded.spans, lowerings=0))
    got = {name: spec.reader(name)(None) for name in want}
    assert got == want
    calls = {s[4]["call"] for s in recorded.spans}
    roots = recorded.named("accl.call.")
    assert len(roots) == len(calls)        # one root span per call
    launches = recorded.named("accl.launch.")
    groups = {s[4]["group"] for s in launches}
    assert len(groups) == len(launches)    # one launch per collective
    if len(chips) == 4:
        # every bucket of the recorded step: 4 deposits, 3 waits, 1 launch
        assert len(launches) == 52
        assert len(recorded.named("accl.deposit")) == 4 * 52
        assert len(recorded.named("accl.wait")) == 3 * 52


def test_innermost_span_of_a_thread():
    """A thread's nested spans flattened to the innermost one at each
    time, and None between them."""
    times, names = spans._innermost([
        (0, 100, "chipbench.call.allreduce"), (10, 90, "accl.call.allreduce"),
        (20, 30, "accl.deposit"), (40, 80, "accl.launch.allreduce"),
        (50, 60, "accl.dispatch"), (120, 130, "chipbench.wait")])

    def at(t):
        return names[bisect.bisect_right(times, t) - 1]

    assert [at(t) for t in (5, 15, 25, 35, 45, 55, 70, 85, 95, 110, 125)] \
        == ["chipbench.call.allreduce", "accl.call.allreduce", "accl.deposit",
            "accl.call.allreduce", "accl.launch.allreduce", "accl.dispatch",
            "accl.launch.allreduce", "accl.call.allreduce",
            "chipbench.call.allreduce", None, "chipbench.wait"]


@pytest.mark.parametrize("launcher", [True, False])
def test_idle_gaps_named_by_program_span(launcher):
    """Named among the program's spans too, the idle time the benchmark's
    reduction puts under the call spans is the program's: what its call,
    launch, assemble and dispatch were doing, on the launching thread or
    on any."""
    path = str(DATA / "ddp4.spans.xplane.pb")
    plain = dict(reduce_trace(path, [0, 1, 2, 3]).top("gap_s"))
    named = dict(spans.idle_gaps(path, [0, 1, 2, 3], launcher=launcher))
    assert set(plain) == {"chipbench.call.allreduce", "chipbench.wait",
                          "chipbench.window"}
    program = sum(s for n, s in named.items() if n.startswith("accl."))
    assert program >= 0.8 * plain["chipbench.call.allreduce"]
    assert sum(named.values()) == pytest.approx(sum(plain.values()))
    assert named["chipbench.wait"] == pytest.approx(plain["chipbench.wait"])


@pytest.mark.parametrize("trace", sorted(RECORDED))
def test_span_traces_leave_the_accepted_reduction_alone(trace):
    """The accepted reduction of a trace that carries the program's spans
    still names idle time by the benchmark's spans alone."""
    red = reduce_trace(str(DATA / trace), RECORDED[trace][0])
    assert all(n.startswith("chipbench.") for n, _ in red.top("gap_s"))
    assert red.busy_s + sum(s for _, s in red.top("gap_s")) == \
        pytest.approx(red.window_s)
