"""Median microseconds of host staging per call, over the calls that stage:
each call's ``accl.stage.*`` spans (the D2H read of an operand, the numpy
combine, the landing of the result) summed by call id."""

from chipbench import spans


def read(run):
    s = spans.from_program()
    return None if s is None else s.median_per_call_us("accl.stage.")
