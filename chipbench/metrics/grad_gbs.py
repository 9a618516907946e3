"""Gradient bytes per chip completed over the whole window, in GB/s: the
result bytes of every call one rank issued in the window's steps, over the
window from its start to the end of its last step (host clock)."""


def read(run):
    w = run.window
    return w.bytes / w.seconds / 1e9 if w.steps and w.seconds > 0 else None
