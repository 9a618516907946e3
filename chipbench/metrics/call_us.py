"""Microseconds per call: the window, from its start to the end of its last
step, over the calls one rank issued in it (host clock). Each call is
issued once the one before it has its result, so this is the mean time from
issue to a ready result, taken over all the calls and all the time."""


def read(run):
    w = run.window
    return w.seconds / w.calls * 1e6 if w.calls and w.seconds > 0 else None
