"""Median microseconds a rank waited in a synchronous collective for its
last peer and the launch: the program's ``accl.wait`` spans (a rank whose
call had retired when it began to wait records none)."""

from chipbench import spans


def read(run):
    s = spans.from_program()
    return None if s is None else s.median_us("accl.wait")
