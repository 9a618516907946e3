"""The one generator of traffic: what a cell asks the library to do, from data.

A traffic file (``chipbench/traffic/<name>.json``) describes one step of a
closed loop; the configuration gives the buffers (``buffers/<rule>.py``) and
their type. Keys:

* ``step``: phases, issued in order on every rank. A phase names its calls
  (``ops``: ``op``, integer ``weight``, ``options`` passed to the call) and
  its ``size``: ``"each"`` issues one call per buffer slot, in slot order;
  ``"draw"`` issues one call whose op and slot are drawn from the seed.
  Draws are balanced: every block of draws holds each (op, slot) pair
  ``weight`` times, in an order the seed shuffles, so every seed asks for
  the same work.
* ``operands``: ``"rotate"`` feeds a step's calls from input set
  ``step % operand_sets``; ``"draw"`` draws each operand's set.
* ``steps``: the length of the seeded cycle of steps the window runs
  through (it starts over when the window outlasts it).
* ``warm_steps``: steps run after the warm-up's one call of every (op,
  slot), before the window.
* ``sample``: each call is compared with probability ``1/every`` (and
  at least one call of the cycle is); a run keeps the first ``max`` such
  calls of the window and those of its last step.
* ``limits``: per configuration dtype, the comparison's limits.
* ``placement`` (optional): ``"device"`` (the default) or ``"host"``,
  where every operand and result lives (``drive.py``).

A step ends when every rank's results of it are ready. Everything drawn here
comes from ``--seed`` alone: the same seed gives the same calls, sizes,
operands and samples.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from . import data, spec

MAX_OPERANDS = 2


def rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), salt])


def sizes(config: dict) -> list[int]:
    """Elements of each buffer slot of the configuration."""
    itemsize = data.dtype(config["dtype"]).itemsize
    return spec.module("buffers", config["buffers"]).sizes(config, itemsize)


@dataclasses.dataclass(frozen=True)
class Plan:
    entries: tuple        # (op name, options) of each call kind
    op: np.ndarray        # [steps, calls]: index into entries
    slot: np.ndarray      # [steps, calls]: buffer slot
    opnd: np.ndarray      # [steps, calls, MAX_OPERANDS]: input sets
    sampled: np.ndarray   # [steps, calls]: the call's result is compared

    @property
    def steps(self) -> int:
        return self.op.shape[0]

    def rows(self) -> list:
        """Per step: its calls as (entry, slot, (sets...), sampled)."""
        return [list(zip(self.op[k].tolist(), self.slot[k].tolist(),
                         map(tuple, self.opnd[k].tolist()),
                         self.sampled[k].tolist()))
                for k in range(self.steps)]


def _balanced(g, pairs: list, n: int) -> np.ndarray:
    """``n`` draws from ``pairs`` in shuffled blocks of all of them."""
    blocks = -(-n // len(pairs))
    order = np.argsort(g.random((blocks, len(pairs))), axis=1).ravel()
    return np.asarray(pairs)[order[:n]]


def plan(traffic: dict, n_slots: int, seed: int) -> Plan:
    steps = int(traffic["steps"])
    sets = int(traffic["operand_sets"])
    g = rng(seed, 1)
    entries, op_cols, slot_cols = [], [], []
    for phase in traffic["step"]:
        idx = []
        for o in phase["ops"]:
            key = (o["op"], o.get("options", {}))
            if key not in entries:
                entries.append(key)
            idx += [entries.index(key)] * int(o["weight"])
        if phase["size"] == "each":
            ops = _balanced(g, idx, steps * n_slots).reshape(steps, n_slots)
            op_cols.append(ops)
            slot_cols.append(np.tile(np.arange(n_slots), (steps, 1)))
        elif phase["size"] == "draw":
            pairs = [(e, s) for e in idx for s in range(n_slots)]
            drawn = _balanced(g, pairs, steps)
            op_cols.append(drawn[:, :1])
            slot_cols.append(drawn[:, 1:])
        else:
            raise ValueError(f"phase size {phase['size']!r}: 'each' or 'draw'")
    op = np.concatenate(op_cols, axis=1)
    calls = op.shape[1]
    if traffic["operands"] == "rotate":
        opnd = np.broadcast_to((np.arange(steps) % sets)[:, None, None],
                               (steps, calls, MAX_OPERANDS)).copy()
    elif traffic["operands"] == "draw":
        opnd = g.integers(0, sets, (steps, calls, MAX_OPERANDS))
    else:
        raise ValueError(f"operands {traffic['operands']!r}: 'rotate' or "
                         f"'draw'")
    u = g.random((steps, calls))
    sampled = u < 1.0 / traffic["sample"]["every"]
    sampled.flat[u.argmin()] = True      # never a cycle with nothing compared
    return Plan(entries=tuple(entries), op=op,
                slot=np.concatenate(slot_cols, axis=1), opnd=opnd,
                sampled=sampled)
