"""``ACCL.combine`` (SUM) of two operands of the calling rank."""

OPERANDS = 2
RESULT = True


def issue(a, srcs, dst, n: int, options: dict) -> None:
    from accl_tpu.constants import ReduceFunc
    a.combine(n, ReduceFunc.SUM, srcs[0], srcs[1], dst, **options)


def terms(xs, rank: int) -> list:
    return [xs[rank][0], xs[rank][1]]
