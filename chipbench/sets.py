"""Run one cell several times, one process after another, and give its spread.

``python -m chipbench.sets --workload <name> --seeds 1,2,3 --seconds 20
[--trace 0] --out <path>``

Each run is ``python -m chipbench`` in a process of its own, as a check
runs it. ``<path>.jsonl`` gets one line per run: the seed, the exit code,
the wall seconds, the run's result line and the last lines of its stderr.
The summary line gives, for each metric, the median and the spread: the
distance between the first and third quartiles (``statistics.quantiles``)
over the median (None where the median is 0). A run that fails or is not
correct ends the set.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

from . import spec


def spread(values) -> float | None:
    """None where the median is 0 (a count such as lowerings), which has
    no spread relative to it."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q3 - q1) / median if median else None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench.sets")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = Path(args.out + ".jsonl")
    out.parent.mkdir(parents=True, exist_ok=True)
    values: dict = {}
    with open(out, "a") as f:
        for seed in [int(s) for s in args.seeds.split(",")]:
            t = time.perf_counter()
            p = subprocess.run(
                [sys.executable, "-m", "chipbench", "--workload",
                 args.workload, "--seed", str(seed), "--seconds",
                 str(args.seconds), "--trace", str(args.trace)],
                cwd=spec.ROOT, capture_output=True, text=True)
            lines = p.stdout.strip().splitlines()
            line = json.loads(lines[-1]) if p.returncode == 0 and lines \
                else None
            rec = {"seed": seed, "trace": args.trace, "rc": p.returncode,
                   "wall_s": time.perf_counter() - t, "line": line,
                   "stderr": p.stderr.strip().splitlines()[-12:]}
            f.write(json.dumps(rec) + "\n")
            f.flush()
            print(json.dumps(rec), flush=True)
            if line is None or not line["correct"]:
                return 1
            for k, m in line["metrics"].items():
                values.setdefault(k, []).append(m["value"])
    summary = {k: {"median": statistics.median(v),
                   "spread": spread(v) if len(v) > 1 else None,
                   "values": v}
               for k, v in values.items()}
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
