"""Seconds from the start of the process to the window: JAX, the ranks'
drivers, buffers made on the chips and the warm-up (and, in a checkout's
first run, compilation)."""


def read(run):
    return run.setup_s
