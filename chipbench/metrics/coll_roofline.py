"""Percent of the roofline the collectives reach: the least time the chips
could take for the window's allreduce calls over the device time of the
collective operations in the trace, per chip. The least time of a call is
the larger of its interconnect bound, the bytes each chip sends over the
chip's published aggregate ICI bandwidth, and its HBM bound
(``peaks.allreduce_least_s``). The aggregate counts every ICI port of a
chip, more than a 2x2 slice may be able to use, so this is a lower bound on
the share of the reachable peak (PERF.md, "Layers")."""

from chipbench import peaks


def read(run):
    world = run.cell.chips
    spent = run.trace.collective_s if run.trace else 0.0
    calls = {n: c for (op, n), c in run.window.issued.items()
             if op == "allreduce"}
    if world < 2 or not calls or spent <= 0:
        return None
    least = sum(c * peaks.allreduce_least_s(n, world, run.peak,
                                            run.window.itemsize)[0]
                for n, c in calls.items())
    return 100.0 * least / spent
