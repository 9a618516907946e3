"""Percent of the traced window in which no operation ran on the chip,
averaged over the cell's chips (1 - busy / window)."""


def read(run):
    return run.idle_percent()
