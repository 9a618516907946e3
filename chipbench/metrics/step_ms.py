"""Milliseconds per step: the window, from its start to the end of its last
step, over the steps completed in it (host clock). In a decode cell a step
is one token: every call the token waits on, issued back to back, until
every rank's results are ready."""


def read(run):
    w = run.window
    return w.seconds / w.steps * 1e3 if w.steps and w.seconds > 0 else None
