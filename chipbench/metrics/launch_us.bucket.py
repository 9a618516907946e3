"""Median microseconds of the collective launches in the window: the
program's ``accl.launch.*`` spans, on the thread of the rank that completes
a rendezvous group, from its claim to every member's completion (assemble,
dispatch, rebind)."""

from chipbench import spans


def read(run):
    s = spans.from_program()
    return None if s is None else s.median_us("accl.launch.")
