"""``ACCL.copy`` of one operand of the calling rank."""

OPERANDS = 1
RESULT = True


def issue(a, srcs, dst, n: int, options: dict) -> None:
    a.copy(srcs[0], dst, n, **options)


def terms(xs, rank: int) -> list:
    return [xs[rank][0]]
