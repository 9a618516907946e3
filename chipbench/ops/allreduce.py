"""``ACCL.allreduce`` (SUM) of one operand over every rank of the cell.

``options`` are the call's keywords as the traffic gives them; an
``algorithm`` is named as in ``CollectiveAlgorithm`` (``"AUTO"``)."""

OPERANDS = 1
RESULT = True


def issue(a, srcs, dst, n: int, options: dict) -> None:
    from accl_tpu.constants import CollectiveAlgorithm, ReduceFunc
    kw = dict(options)
    if "algorithm" in kw:
        kw["algorithm"] = CollectiveAlgorithm[kw["algorithm"]]
    a.allreduce(srcs[0], dst, n, ReduceFunc.SUM, **kw)


def terms(xs, rank: int) -> list:
    """What the result sums: every rank's operand (``xs[rank][k]`` is
    rank's k-th operand)."""
    return [x[0] for x in xs]
