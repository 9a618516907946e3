"""Drive a cell's traffic through the library's normal path.

Every cell runs ``tpu_world(chips)``: one ``ACCL`` driver per rank, one
thread per rank (as ``accl_tpu.testing.run_ranks`` does), buffers made from
the seed, and the synchronous call API. The traffic's ``placement`` key
says where the buffers live: ``"device"`` (the default), device-resident
arrays made on each rank's chip; ``"host"``, host-mirror buffers made from
numpy arrays, the driver's default buffer mode, whose calls stage through
the host. Each call is issued by its ``ops/<op>.py``. The results of the
sampled calls are kept for the comparison with the plain reference
(``check.py``), which runs after the window; a host result is copied out
of its buffer only where it is kept, and the last step's after the window
has closed.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import threading
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from accl_tpu.testing import run_ranks

from . import data, spec
from . import traffic as tr

BARRIER_S = 600.0
PLACEMENTS = ("device", "host")  # where a traffic's buffers live


def _no_span(name):
    return contextlib.nullcontext()


def spans(on: bool):
    """Host spans around the calls into the library: the profiler's own
    ``TraceAnnotation`` when tracing, nothing otherwise."""
    return jax.profiler.TraceAnnotation if on else _no_span


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(keys, sizes, dt):
    """One row of seeded buffers per row of ``keys``, then a row of
    zeros, on the chip that holds ``keys``."""
    made = tuple(tuple(data.values(jnp, keys[s, i], n, dt)
                       for i, n in enumerate(sizes))
                 for s in range(keys.shape[0]))
    return made + (tuple(jnp.zeros(n, dt) for n in sizes),)


def make_rows(a, seed: int, sets: int, sizes, dt, host: bool = False
              ) -> list:
    """Input sets ``0 .. sets-1`` of buffers sized ``sizes`` for rank
    ``a``, then a row of zeros: device arrays, or numpy arrays where
    ``host``."""
    if host:
        return [[data.values(np, data.stream_key(seed, s, a.rank, i), n, dt)
                 for i, n in enumerate(sizes)] for s in range(sets)] + [
            [np.zeros(n, dt) for n in sizes]]
    keys = np.array([[data.stream_key(seed, s, a.rank, i)
                      for i in range(len(sizes))] for s in range(sets)],
                    np.uint32).reshape(sets, len(sizes))
    return list(_make(jax.device_put(keys, a.device.my_device),
                      tuple(sizes), np.dtype(dt)))


@dataclasses.dataclass
class Window:
    """What one rank did in the window, on the host clock."""
    steps: int
    seconds: float           # from the start line to the last step's end
    step_k: list             # plan step of each window step
    step_s: list             # seconds of each window step
    calls: int               # calls one rank issued
    bytes: int               # result bytes one rank's calls produced
    issued: collections.Counter   # (op, elements) -> calls of one rank
    itemsize: int


class Run:
    """A cell's buffers on every rank, and the steps of its plan."""

    def __init__(self, accls, cell: spec.Cell, seed: int):
        self.accls = accls
        self.traffic = cell.traffic
        self.dt = data.dtype(cell.config["dtype"])
        where = cell.traffic.get("placement", "device")
        if where not in PLACEMENTS:
            raise ValueError(f"placement {where!r}: one of {PLACEMENTS}")
        self.host = where == "host"
        self.sizes = tr.sizes(cell.config)
        self.plan = tr.plan(cell.traffic, len(self.sizes), seed)
        self.rows = self.plan.rows()
        self.ops = [spec.module("ops", name) for name, _ in self.plan.entries]
        self.k = 0              # steps completed, warm-up included
        self.failed = 0
        self.abort = False
        self._failed_lock = threading.Lock()
        self.samples = {}       # (step, call) -> every rank's result
        self._keep = int(cell.traffic["sample"]["max"])
        self.ins, self.outs = [], []
        for a in accls:
            made = make_rows(a, seed, int(cell.traffic["operand_sets"]),
                             self.sizes, self.dt, self.host)
            self.ins.append([[a.buffer(data=x) for x in row]
                             for row in made[:-1]])
            self.outs.append([a.buffer(data=z) for z in made[-1]])
        self._work = []         # per plan step: (calls, bytes, issued)
        for row in self.rows:
            nbytes = sum(self.sizes[s] for e, s, _, _ in row
                         if self.ops[e].RESULT) * self.dt.itemsize
            self._work.append((len(row), nbytes, collections.Counter(
                (self.plan.entries[e][0], self.sizes[s])
                for e, s, _, _ in row)))

    def _issue(self, a, call, span):
        """One call on rank ``a``; its destination if it has a result."""
        e, s, sets, _ = call
        op = self.ops[e]
        srcs = [self.ins[a.rank][sets[j]][s] for j in range(op.OPERANDS)]
        dst = self.outs[a.rank][s]
        with span(f"chipbench.call.{self.plan.entries[e][0]}"):
            op.issue(a, srcs, dst, self.sizes[s], self.plan.entries[e][1])
        return dst if op.RESULT else None

    def warm(self):
        """Every (op, slot) of the plan once on every rank, then
        ``warm_steps`` steps of the plan: every program shape is built
        and the window starts steady. Nothing here is sampled."""
        seen = sorted({(e, s) for row in self.rows for e, s, _, _ in row})
        sets = (0, min(1, int(self.traffic["operand_sets"]) - 1))
        self.run(steps=1, rows=[[(e, s, sets, False) for e, s in seen]])
        self.run(steps=int(self.traffic["warm_steps"]))

    def run(self, *, seconds: float | None = None, steps: int | None = None,
            span=_no_span, rows=None) -> Window:
        """Steps on every rank until ``steps`` are done or ``seconds`` have
        passed; the window closes at the end of the step in flight. Only a
        timed run (``seconds``) samples results."""
        sample = rows is None and seconds is not None
        rows = self.rows if rows is None else rows
        st = {"t0": None, "t1": None, "stop": False}
        win = Window(0, 0.0, [], [], 0, 0, collections.Counter(),
                     self.dt.itemsize)

        def arrive():  # run by the last rank to reach the barrier
            now = time.perf_counter()
            if st["t0"] is None:
                st["t0"] = now
            else:
                pk = self.k % len(rows)
                calls, nbytes, issued = (self._work[pk] if rows is self.rows
                                         else (len(rows[pk]), 0, {}))
                win.steps += 1
                win.step_k.append(pk)
                win.step_s.append(now - st["t1"])
                win.calls += calls
                win.bytes += nbytes
                win.issued.update(issued)
                self.k += 1
            st["t1"] = now
            st["stop"] = (self.abort
                          or (steps is not None and win.steps >= steps)
                          or (seconds is not None
                              and now - st["t0"] >= seconds))

        barrier = threading.Barrier(len(self.accls), action=arrive,
                                    timeout=BARRIER_S)

        def loop(a):
            flagged, last = 0, []
            barrier.wait()
            while not st["stop"]:
                k = self.k
                row = rows[k % len(rows)]
                got = []
                try:
                    with span("chipbench.step"):
                        dsts = []
                        for c, call in enumerate(row):
                            dst = self._issue(a, call, span)
                            if dst is None:
                                continue
                            if sample and call[3]:
                                keep = flagged + len(got) < self._keep
                                got.append(((k, c), dst, self._result(dst)
                                            if keep or not self.host
                                            else None))
                            if not self.host:   # a host result is in place
                                dsts.append(dst)
                        with span("chipbench.wait"):
                            for d in dsts:
                                d.jax.block_until_ready()
                except Exception:  # noqa: BLE001 -- counted, run ends
                    traceback.print_exc()
                    with self._failed_lock:
                        self.failed += 1
                    self.abort = True
                for key, _, arr in got[:max(self._keep - flagged, 0)]:
                    self._kept(key)[a.rank] = arr
                flagged += len(got)
                last = got
                barrier.wait()
            seen = set()    # a host buffer holds its slot's last result
            for key, dst, arr in reversed(last):  # the last step's samples
                if arr is None and id(dst) in seen:
                    continue
                seen.add(id(dst))
                self._kept(key)[a.rank] = (self._result(dst) if arr is None
                                           else arr)

        run_ranks(self.accls, loop, timeout=BARRIER_S + (seconds or 0))
        win.seconds = st["t1"] - st["t0"]
        return win

    def _result(self, dst):
        """A result as it stands: a host mirror's copied out of the buffer
        the next call into its slot overwrites, a device array as is."""
        return dst.data.copy() if self.host else dst.jax

    def _kept(self, key) -> list:
        return self.samples.setdefault(key, [None] * len(self.accls))

    def results(self) -> dict:
        """Every sampled call's results on the host, per rank (None where
        a rank's result is missing); then the buffers are freed."""
        out = {key: [None if x is None else np.asarray(x) for x in arrs]
               for key, arrs in sorted(self.samples.items())}
        for r in range(len(self.accls)):
            for b in self.outs[r] + [x for row in self.ins[r] for x in row]:
                b.free_buffer()
        self.ins = self.outs = self.samples = None
        return out
