"""The program's own spans (``accl.*``) and what per-layer metrics read from
them.

A traced run profiles its window (``ACCL.start_profiling``), which arms the
program's spans: each is a ``TraceAnnotation`` on the device trace's clock
and an entry that the program keeps in memory for its last profiled
interval (``accl_tpu.tracing.SPANS``). A reader reads the entries, since a
run has removed its trace file by the time the readers run; a program that
keeps no spans gives None. A recorded trace holds the same spans, with the
trace line standing for the thread (:meth:`Spans.from_trace`): the readers'
tests and ``tests/record_spans.py`` read them there.

A span is ``(name, thread, start_ns, end_ns, args)``. The spans of one call
share ``args["call"]``; a span's parent is the innermost span around it on
its own thread. :func:`idle_gaps` names the chips' idle time by the spans
of a recorded trace, on the thread whose launch the chips wait for.
"""

from __future__ import annotations

import bisect
import collections
import statistics

from jax.profiler import ProfileData

from chipbench import trace_reduce

PREFIX = "accl."


def _host_events(pd: ProfileData):
    """``(line index, event)`` of every event on the host plane."""
    for plane in pd.planes:
        if plane.name == trace_reduce.HOST_PLANE:
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    yield i, e


def _window(pd: ProfileData, path: str) -> tuple[int, int]:
    windows = [(e.start_ns, e.end_ns) for _, e in _host_events(pd)
               if e.name == trace_reduce.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} window spans")
    return windows[0]


class Spans:
    def __init__(self, spans):
        self.spans = [s for s in spans if s[0].startswith(PREFIX)]

    @classmethod
    def from_trace(cls, path: str) -> "Spans":
        """The ``accl.*`` events of the host plane of the trace at
        ``path``, their stats as args, inside the window span."""
        pd = ProfileData.from_file(path)
        lo, hi = _window(pd, path)
        return cls((e.name, i, e.start_ns, e.end_ns, dict(e.stats))
                   for i, e in _host_events(pd)
                   if e.name.startswith(PREFIX)
                   and e.start_ns >= lo and e.end_ns <= hi)

    def named(self, prefix: str) -> list:
        return [s for s in self.spans if s[0].startswith(prefix)]

    def median_us(self, prefix: str) -> float | None:
        """Median duration of the spans whose names start with
        ``prefix``."""
        durs = [(s[3] - s[2]) * 1e-3 for s in self.named(prefix)]
        return statistics.median(durs) if durs else None

    def self_us(self, prefix: str) -> list[float]:
        """Self time of each span named ``prefix*``: its duration minus the
        union of the spans inside it on its thread. Spans of a thread nest,
        so that union is the sum of its children's durations."""
        child = collections.Counter()
        by_thread = collections.defaultdict(list)
        for s in self.spans:
            by_thread[s[1]].append(s)
        for spans in by_thread.values():
            stack = []
            for s in sorted(spans, key=lambda s: (s[2], -s[3])):
                while stack and stack[-1][3] <= s[2]:
                    stack.pop()
                if stack:
                    child[id(stack[-1])] += s[3] - s[2]
                stack.append(s)
        return [(s[3] - s[2] - child[id(s)]) * 1e-3
                for s in self.named(prefix)]

    def median_self_us(self, prefix: str) -> float | None:
        selfs = self.self_us(prefix)
        return statistics.median(selfs) if selfs else None

    def per_call_us(self, prefix: str) -> dict:
        """Summed duration of the spans named ``prefix*``, by call id."""
        out = collections.Counter()
        for s in self.named(prefix):
            out[s[4].get("call")] += (s[3] - s[2]) * 1e-3
        return dict(out)

    def median_per_call_us(self, prefix: str) -> float | None:
        """Median, over the calls that have any span named ``prefix*``, of
        that call's summed time in them."""
        sums = list(self.per_call_us(prefix).values())
        return statistics.median(sums) if sums else None


def _program_log():
    from accl_tpu import tracing
    return getattr(tracing, "SPANS", None)


def from_program() -> Spans | None:
    """The spans of the program's last profiled interval; None where the
    program keeps none. A reading fails where the program dropped spans
    past its capacity, rather than read a part of the window."""
    log = _program_log()
    if log is None:
        return None
    if getattr(log, "dropped", 0):
        raise ValueError(f"the program dropped {log.dropped} spans past "
                         "its capacity")
    return Spans(log.spans())


def lowerings() -> int | None:
    """JAX lowerings over the program's last profiled interval; None where
    the program does not count them."""
    return getattr(_program_log(), "lowerings", None)


def _innermost(spans) -> tuple[list, list]:
    """One thread's spans ``(start, end, name)``, which nest, flattened:
    ``(times, names)``, the innermost span's name (None outside every
    span) from each time on until the next."""
    times, names, stack = [], [], []
    for s, e, n in sorted(spans, key=lambda sp: (sp[0], -sp[1])):
        while stack and stack[-1][1] <= s:
            times.append(stack.pop()[1])
            names.append(stack[-1][2] if stack else None)
        stack.append((s, e, n))
        times.append(s)
        names.append(n)
    while stack:
        times.append(stack.pop()[1])
        names.append(stack[-1][2] if stack else None)
    return times, names


def idle_gaps(path: str, device_ids, k: int = 20,
              launcher: bool = True) -> list:
    """The chips' idle seconds in the window of the trace at ``path``,
    named among the benchmark's spans and the program's: ``[[name,
    seconds], ...]``, averaged over the chips ``device_ids``.

    With ``launcher``, a gap is split over the innermost spans of one
    thread, by the time each covers: the thread of the collective launch
    the chips wait for, that of the first ``accl.launch.*`` to end after
    the gap's middle. A rank blocked in ``accl.wait`` on another thread
    covers every gap of its collective and would take it from what the
    launching thread was doing. Without, or for the part of a gap that no
    span of that thread covers, the gap goes to the shortest span covering
    its middle on any thread, as ``trace_reduce`` names it."""
    pd = ProfileData.from_file(path)
    lo, hi = _window(pd, path)
    by_line = collections.defaultdict(list)
    for i, e in _host_events(pd):
        if e.name.startswith((trace_reduce.SPAN_PREFIX, PREFIX)):
            by_line[i].append((e.start_ns, e.end_ns, e.name))
    every = [sp for spans in by_line.values() for sp in spans]
    flat = {i: _innermost(spans) for i, spans in by_line.items()}
    launches = sorted((e, i) for i, spans in by_line.items()
                      for s, e, n in spans if n.startswith("accl.launch."))
    ends = [e for e, _ in launches]

    def split(gap, out: collections.Counter) -> None:
        s, e = gap
        rest = e - s
        j = bisect.bisect_left(ends, (s + e) / 2)
        if launcher and j < len(ends):
            times, names = flat[launches[j][1]]
            at = max(bisect.bisect_right(times, s) - 1, 0)
            while at < len(times) and times[at] < e:
                end = times[at + 1] if at + 1 < len(times) else e
                part = min(end, e) - max(times[at], s)
                if names[at] and part > 0:
                    out[names[at]] += part * 1e-9
                    rest -= part
                at += 1
        if rest > 0:
            (n,) = trace_reduce._name_gaps([gap], every)
            out[n] += rest * 1e-9

    want = {int(d) for d in device_ids}
    total, chips = collections.Counter(), 0
    for plane in pd.planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) not in want:
            continue
        ops = [iv for line in plane.lines
               if line.name in trace_reduce.OP_LINES
               for e in line.events
               for iv in trace_reduce._clip([(e.start_ns, e.end_ns)], lo, hi)]
        edges = [lo] + [t for iv in trace_reduce._union(ops) for t in iv] + [hi]
        for g in zip(edges[::2], edges[1::2]):
            if g[1] > g[0]:
                split(g, total)
        chips += 1
    if chips != len(want):
        raise ValueError(f"{path}: found planes for {chips} of the chips "
                         f"{sorted(want)}")
    return [[n, s / chips] for n, s in total.most_common(k)]
