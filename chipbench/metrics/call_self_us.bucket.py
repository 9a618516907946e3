"""Median self time, in microseconds, of the driver's calls in the window:
each ``accl.call.*`` span's duration minus the union of the program's spans
inside it on its thread (the deposit, wait, launch and host staging)."""

from chipbench import spans


def read(run):
    s = spans.from_program()
    return None if s is None else s.median_self_us("accl.call.")
