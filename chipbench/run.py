"""One run of one cell: set-up, a measured window, the comparison, one line.

``python -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>``

Set-up counts from the start of the process: JAX, ``tpu_world``, buffers
made on the chips from the seed, and a warm-up that runs every program
shape the window will use. The window then runs for ``--seconds`` and
closes at the end of the step in flight. With ``--trace 0`` the line
carries the cell's end-to-end metrics; with ``--trace 1`` the window runs
under the profiler and the driver's call profiler, the program's launch-plan
counter is read at its start and end, and the line carries the per-layer
metrics. Each metric is read by ``metrics/<name>.py``. The program's
results are compared with the plain reference (``check.py``) after the
window, once the program's buffers are freed; each number compared is
printed with its limit, last on stderr and last in the line.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import os
import resource
import shutil
import statistics
import sys
import time

import jax

from . import check, drive, peaks, spec
from .trace_reduce import reduce_trace

CACHE_DIR = spec.ROOT / ".jax_cache"
TRACE_DIR = spec.ROOT / ".chipbench" / "trace"


@dataclasses.dataclass
class Reading:
    """What a metric's reader gets from a run."""
    cell: spec.Cell
    setup_s: float
    window: drive.Window
    peak: dict              # peaks.PEAKS row of the device kind
    records: list | None    # traced: every rank's driver CallRecords
    trace: object | None    # traced: trace_reduce.Reduction of the window
    plan_launches: dict | None = None   # traced: launches by plan result

    def median_call_us(self) -> float | None:
        durs = [r.duration_us for r in self.records or ()]
        return statistics.median(durs) if durs else None

    def idle_percent(self) -> float | None:
        if self.trace is None:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.window_s)

    def plan_hit_percent(self) -> float | None:
        got = self.plan_launches or {}
        total = sum(got.get(k, 0) for k in ("hit", "miss", "fallback"))
        return 100.0 * got.get("hit", 0) / total if total > 0 else None


def _parse(argv):
    ap = argparse.ArgumentParser(prog="python -m chipbench",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def devices(chips: int):
    """The cell's chips; a host without ``chips`` TPUs is an error."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"chipbench: the cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} {d0.platform} device(s) of kind "
            f"{d0.device_kind!r}")
    return devs[:chips]


def compile_cache():
    """JAX's persistent cache at the checkout's fixed ``.jax_cache``, for
    every program, so that only a checkout's first run compiles."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from accl_tpu.utils.platform import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def plan_launches(since: dict | None = None) -> dict:
    """The program's collective launches by launch-plan result
    (``tpu_launch_plan_total{result}``: hit, miss, fallback), so far or
    since the reading ``since``; empty where the program counts none."""
    from accl_tpu.tracing import METRICS
    got = METRICS.snapshot()["counters"].get("tpu_launch_plan_total", {})
    now = {k.split("=", 1)[1]: v for k, v in got.items()}
    return {k: v - (since or {}).get(k, 0) for k, v in now.items()}


def _cpu_s() -> float:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return ru.ru_utime + ru.ru_stime


def _diagnose(r: drive.Run, win: drive.Window, cpu_s: float) -> None:
    """Where the window's host time went, on stderr: the mean step by the
    ops it issues, and the CPU time the process used in the window."""
    by = collections.defaultdict(list)
    for pk, s in zip(win.step_k, win.step_s):
        by[" ".join(r.plan.entries[e][0] for e, *_ in r.rows[pk])[:40]].append(s)
    for sig, ss in sorted(by.items()):
        print(f"steps [{sig}]: {len(ss)} x {statistics.fmean(ss) * 1e6:.1f} us",
              file=sys.stderr)
    print(f"host: cpu {cpu_s:.3f} s in a {win.seconds:.3f} s window",
          file=sys.stderr)


def main(argv=None, t_start: float | None = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = _parse(argv)
    cell = spec.find_cell(args.workload)
    limits = cell.traffic["limits"][cell.config["dtype"]]
    compile_cache()
    from accl_tpu.device.tpu import tpu_world
    devs = devices(cell.chips)
    d0 = devs[0]
    accls = tpu_world(cell.chips)
    r = drive.Run(accls, cell, args.seed)
    r.warm()
    setup_s = time.perf_counter() - t_start

    traced = bool(args.trace)
    span = drive.spans(traced)
    trace_dir = TRACE_DIR / args.workload
    plans = None
    if traced:
        plans = plan_launches()
        shutil.rmtree(trace_dir, ignore_errors=True)
        for a in accls:
            a.profiler.clear()
            a.start_profiling()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    cpu0 = _cpu_s()
    try:
        with span("chipbench.window"):
            win = r.run(seconds=args.seconds, span=span)
    finally:
        if traced:
            jax.profiler.stop_trace()
    cpu_s = _cpu_s() - cpu0
    if traced:
        plans = plan_launches(since=plans)
    failed = r.failed
    attempted = win.calls * len(accls)
    records = None
    if traced:
        records = []
        for a in accls:
            a.end_profiling()
            records += [x for x in a.profiler.records if x.op != "config"]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": max(d.memory_stats()["peak_bytes_in_use"]
                                       for d in devs)}
    results = r.results()
    for a in accls:
        a.deinit()
    ref = check.Reference(cell, args.seed, len(accls))
    checks = {"result_err": (check.result_err(ref, results),
                             limits["result_err"])}
    correct = (failed == 0 and attempted > 0
               and all(v <= lim for v, lim in checks.values()))

    line = {"correct": correct, "attempted": attempted, "failed": failed}
    red = None
    if traced:
        path = next(trace_dir.glob("**/*.xplane.pb"))
        red = reduce_trace(str(path), [d.id for d in devs])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=red.busy_s, window_s=red.window_s)
    reading = Reading(cell=cell, setup_s=setup_s, window=win,
                      peak=peaks.peak_for(d0.device_kind), records=records,
                      trace=red, plan_launches=plans)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = spec.reader(m["name"])(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line.update(metrics=metrics, device=device)
    if traced:
        line["breakdown"] = {"device_ops": red.top("op_s"),
                             "idle_gaps": red.top("gap_s")}
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    _diagnose(r, win, cpu_s)
    print(f"chipbench {args.workload} seed {args.seed} on {cell.chips} x "
          f"{d0.device_kind}: correct {correct}, attempted {attempted}, "
          f"failed {failed}, compared {len(results)} calls", file=sys.stderr)
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
