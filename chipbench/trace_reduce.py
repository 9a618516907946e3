"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

Read with ``jax.profiler.ProfileData``. The names it relies on were read by
hand from TPU v5 lite traces (PERF.md, "Layers"):

* a chip is the plane ``/device:TPU:<id>``; its operations are the events of
  the lines ``XLA Ops`` and ``Async XLA Ops``, each named by its HLO text,
  ``%<instruction> = <type> <opcode>(<operands>), ...``;
* the benchmark's own host spans are events named ``chipbench.*`` on the
  plane ``/host:CPU`` (``jax.profiler.TraceAnnotation``), on the same clock
  as the device; ``chipbench.window`` spans the measured window.

Busy time is the union of a chip's operation intervals inside the window;
an idle gap is named by the innermost benchmark span that covers its middle
(``chipbench.window`` itself where the host was between steps or calls).

``python -m chipbench.trace_reduce <trace.xplane.pb>`` prints the planes,
lines and heaviest events of a trace, for looking at one by hand.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import sys

from jax.profiler import ProfileData

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OP_LINES = ("XLA Ops", "Async XLA Ops")
SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
COLLECTIVES = ("all-reduce", "collective-permute", "all-gather",
               "reduce-scatter", "all-to-all")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_ASYNC_PART = re.compile(r"-(start|done|update)$")


def parse_op(text: str) -> tuple[str, str]:
    """(label, opcode) of an ``XLA Ops`` event name. The label is the
    instruction and its type without layouts: ``copy.1 f32[6553600]``."""
    instr, sep, rhs = text.partition(" = ")
    if not sep:
        return text, text
    instr = instr.lstrip("%")
    if rhs.startswith("("):          # tuple type: skip to its close
        depth = 0
        for i, c in enumerate(rhs):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                break
        typ, rest = rhs[:i + 1], rhs[i + 1:]
    else:
        typ, _, rest = rhs.partition(" ")
    opcode = rest.strip().split("(", 1)[0]
    return f"{instr} {_LAYOUT.sub('', typ)}", opcode


def collective_base(opcode: str) -> str | None:
    """``all-reduce`` for ``all-reduce``, ``all-reduce-start`` ...; None
    for an operation that moves nothing between chips."""
    base = _ASYNC_PART.sub("", opcode)
    return base if base in COLLECTIVES else None


def _union(intervals) -> list[tuple[float, float]]:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


@dataclasses.dataclass
class Chip:
    plane: str
    busy_s: float
    op_s: collections.Counter          # label -> seconds
    collective_s: float                # union of collective-op intervals
    gap_s: collections.Counter         # host span name -> idle seconds


@dataclasses.dataclass
class Reduction:
    window_s: float
    chips: list

    def _mean(self, f) -> float:
        return sum(f(c) for c in self.chips) / len(self.chips)

    @property
    def busy_s(self) -> float:
        return self._mean(lambda c: c.busy_s)

    @property
    def collective_s(self) -> float:
        return self._mean(lambda c: c.collective_s)

    def top(self, attr: str, k: int = 10) -> list:
        total = collections.Counter()
        for c in self.chips:
            for name, s in getattr(c, attr).items():
                total[name] += s / len(self.chips)
        return [[name, s] for name, s in total.most_common(k)]


def _spans(pd: ProfileData):
    out = []
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append((e.start_ns, e.end_ns, e.name))
    return out


def _name_gaps(gaps, spans) -> collections.Counter:
    """Idle seconds by the innermost span covering each gap's middle."""
    spans = sorted(spans)
    named = collections.Counter()
    active, j = [], 0
    for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        while j < len(spans) and spans[j][0] <= mid:
            active.append(spans[j])
            j += 1
        active = [sp for sp in active if sp[1] >= mid]
        inner = min(active, key=lambda sp: sp[1] - sp[0], default=None)
        named[inner[2] if inner else "no span"] += (e - s) * 1e-9
    return named


def reduce_trace(path: str, device_ids) -> Reduction:
    """The chips ``device_ids`` of the trace at ``path``, inside the
    window span."""
    pd = ProfileData.from_file(path)
    spans = _spans(pd)
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{path}: {len(windows)} {WINDOW_SPAN} spans")
    lo, hi = windows[0]
    want = {int(d) for d in device_ids}
    chips = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m or int(m.group(1)) not in want:
            continue
        ops, coll = [], []
        op_s = collections.Counter()
        for line in plane.lines:
            if line.name not in OP_LINES:
                continue
            for e in line.events:
                iv = _clip([(e.start_ns, e.end_ns)], lo, hi)
                if not iv:
                    continue
                label, opcode = parse_op(e.name)
                ops.extend(iv)
                op_s[label] += (iv[0][1] - iv[0][0]) * 1e-9
                if collective_base(opcode):
                    coll.extend(iv)
        busy = _union(ops)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        chips.append(Chip(
            plane=plane.name,
            busy_s=sum(e - s for s, e in busy) * 1e-9,
            op_s=op_s,
            collective_s=sum(e - s for s, e in _union(coll)) * 1e-9,
            gap_s=_name_gaps(gaps, spans)))
    if len(chips) != len(want):
        raise ValueError(f"{path}: found planes for {len(chips)} of the "
                         f"chips {sorted(want)}")
    return Reduction(window_s=(hi - lo) * 1e-9, chips=chips)


def describe(path: str, top: int = 8) -> None:
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            total, count = collections.Counter(), collections.Counter()
            for e in line.events:
                total[e.name[:100]] += e.duration_ns
                count[e.name[:100]] += 1
            print(f"  line {line.name!r}: {sum(count.values())} events")
            for name, ns in total.most_common(top):
                print(f"    {count[name]:7d} x {ns * 1e-6:12.3f} ms  {name}")


if __name__ == "__main__":
    describe(sys.argv[1])
