"""Percent of the window's collective launches that ran from their
signature's launch plan: the growth of the program's
``tpu_launch_plan_total`` over the traced window, hits over hits, misses and
fallbacks. None where the program counts no launch there."""


def read(run):
    return run.plan_hit_percent()
