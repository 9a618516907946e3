"""Host cost of one program span, while a profiler trace is being taken.

Usage: ``python scripts/span_cost.py [spans_per_thread]`` starts a JAX
profiler trace (Python tracer off, as the chip benchmark takes it), arms
``tracing.SPANS`` and prints one JSON line: microseconds a span per
thread, best of three, on one thread and on four at once, for a bare
``TraceAnnotation`` (no args; three args as kwargs; three args written
into the name as ``name#k=v,...#``) and for ``tracing.annotate`` (one
arg, three args, and a call's four nested spans counted as one).
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import tempfile
import threading
import time

import jax
from jax.profiler import TraceAnnotation

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from accl_tpu.tracing import SPANS, annotate  # noqa: E402

GROUP, NBYTES = "0:17", 26_214_400


def bare0(i):
    with TraceAnnotation("accl.launch.allreduce"):
        pass


def bare3(i):
    with TraceAnnotation("accl.launch.allreduce", call=i, group=GROUP,
                         nbytes=NBYTES):
        pass


def named3(i):
    with TraceAnnotation(f"accl.launch.allreduce#call={i},group={GROUP},"
                         f"nbytes={NBYTES}#"):
        pass


def span1(i):
    with annotate("accl.dispatch", call=i):
        pass


def span3(i):
    with annotate("accl.launch.allreduce", call=i, group=GROUP,
                  nbytes=NBYTES):
        pass


def call4(i):
    with annotate("accl.call.allreduce", call=i, op="allreduce",
                  nbytes=NBYTES, comm=0):
        with annotate("accl.launch.allreduce", call=i, group=GROUP,
                      nbytes=NBYTES):
            with annotate("accl.assemble", call=i):
                pass
            with annotate("accl.dispatch", call=i):
                pass


KINDS = {"bare0": bare0, "bare3": bare3, "named3": named3, "span1": span1,
         "span3": span3, "call4": call4}


def per_thread_us(fn, threads: int, n: int) -> float:
    """Median over ``threads`` threads, run at once, of each one's wall
    time a span."""
    out = [0.0] * threads
    start = threading.Barrier(threads)

    def work(k):
        start.wait()
        t = time.perf_counter()
        for i in range(n):
            fn(i)
        out[k] = (time.perf_counter() - t) / n * 1e6

    ts = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return statistics.median(out)


def main(n: int) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    res = {}
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            for threads in (1, 4):
                for name, fn in KINDS.items():
                    best = float("inf")
                    for _ in range(3):
                        SPANS.arm()        # each rep starts an empty log
                        try:
                            best = min(best, per_thread_us(fn, threads, n))
                        finally:
                            SPANS.disarm()
                    res[f"{name}.t{threads}"] = round(best, 3)
        finally:
            jax.profiler.stop_trace()
    print(json.dumps({"us_per_span_per_thread": res, "spans_per_thread": n,
                      "device": jax.devices()[0].device_kind}))


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 20_000)
