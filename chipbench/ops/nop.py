"""``ACCL.nop``: the whole call path, and no result to compare."""

OPERANDS = 0
RESULT = False


def issue(a, srcs, dst, n: int, options: dict) -> None:
    a.nop(**options)


def terms(xs, rank: int):
    return None
