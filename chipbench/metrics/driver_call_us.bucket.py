"""Median host microseconds from issue to retire of the driver's calls in
the window (``ACCL.profiler`` CallRecords, every rank). On device-resident
collectives the call retires when its program is enqueued, so this is host
time."""


def read(run):
    return run.median_call_us()
