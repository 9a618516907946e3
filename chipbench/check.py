"""The plain reference, its lower-precision control, and the comparison.

This module imports nothing of the program. From the seed it makes the
plan (``traffic.py``) and every operand of a sampled call again
(``data.values`` under numpy), and sums the terms the call's
``ops/<op>.py`` names in float64. A result's error is its largest gap from
the reference over its elements, measured against the sum of the
magnitudes that went into each element: a bound that rounding in the
configuration's type meets on every element, and a narrower type does not.

The control is the same reference summed in the next type below the
configuration's (``CONTROL``), put where the program's results go. It has
to come out as not correct.
"""

from __future__ import annotations

import concurrent.futures
import os

import numpy as np

from . import data, spec
from . import traffic as tr

# the type the control sums in, for each configuration dtype
CONTROL = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn",
           "float16": "float8_e4m3fn"}
# what a non-finite or missing result reads as: above every limit, and
# valid JSON
NOT_FINITE = float(np.finfo(np.float32).max)
WORKERS = min(8, os.cpu_count() or 1)


class Reference:
    """What the calls of one run should produce, from its seed alone."""

    def __init__(self, cell: spec.Cell, seed: int, world: int):
        self.seed, self.world = seed, world
        self.dt = data.dtype(cell.config["dtype"])
        self.sizes = tr.sizes(cell.config)
        self.plan = tr.plan(cell.traffic, len(self.sizes), seed)
        self.ops = [spec.module("ops", name) for name, _ in self.plan.entries]

    def terms(self, key) -> tuple:
        """(op, per rank: the arrays the result of call ``key`` sums)."""
        k, c = key
        pk = k % self.plan.steps
        op = self.ops[self.plan.op[pk, c]]
        s, sets = int(self.plan.slot[pk, c]), self.plan.opnd[pk, c]
        xs = [[data.values(np, data.stream_key(self.seed, int(sets[j]), r, s),
                           self.sizes[s], self.dt)
               for j in range(op.OPERANDS)] for r in range(self.world)]
        return op, [op.terms(xs, r) for r in range(self.world)]

    def call_err(self, key, got) -> float:
        """Largest error of every rank's result ``got[rank]`` of a call."""
        _, per_rank = self.terms(key)
        worst, memo = 0.0, {}
        for r, ts in enumerate(per_rank):
            if got[r] is None:
                return NOT_FINITE
            ident = tuple(id(t) for t in ts)
            if ident not in memo:
                memo[ident] = (np.sum(ts, axis=0, dtype=np.float64),
                               np.sum(np.abs(ts), axis=0, dtype=np.float64))
            ref, scale = memo[ident]
            e = np.abs(np.asarray(got[r], np.float64).reshape(-1) - ref)
            m = float((e / np.maximum(scale, np.finfo(np.float32).tiny)).max())
            worst = max(worst, m if np.isfinite(m) else NOT_FINITE)
        return worst

    def control(self, key) -> list:
        """The call's results as the control makes them: the terms summed
        in order in the next type below the configuration's."""
        low = np.dtype(CONTROL[self.dt.name])
        _, per_rank = self.terms(key)
        out = []
        for ts in per_rank:
            acc = ts[0].astype(low)
            for t in ts[1:]:
                acc = (acc.astype(np.float32)
                       + t.astype(low).astype(np.float32)).astype(low)
            out.append(acc.astype(self.dt))
        return out


def result_err(ref: Reference, results: dict) -> float:
    """The largest error over every compared call; a run with nothing to
    compare reads as failing."""
    if not results:
        return NOT_FINITE
    with concurrent.futures.ThreadPoolExecutor(WORKERS) as pool:
        errs = pool.map(lambda kv: ref.call_err(*kv), results.items())
        return max(errs)


def control_results(ref: Reference, keys) -> dict:
    """The control, shaped as a run's ``results``."""
    with concurrent.futures.ThreadPoolExecutor(WORKERS) as pool:
        return dict(zip(keys, pool.map(ref.control, keys)))
