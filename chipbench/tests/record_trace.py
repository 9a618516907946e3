"""Record a short chip trace of one cell's window, for the reduction's test.

``python -m chipbench.tests.record_trace --workload <name> --seconds 0.05
--out <file.xplane.pb>`` runs the cell's set-up, then a traced window of
``--seconds``, copies the trace to ``--out`` and prints what
``trace_reduce`` reads from it. The committed ``data/*.xplane.pb`` came
from this script on a TPU v5 lite host, with the checkout's path in their
source locations overwritten by one of the same length.
"""

from __future__ import annotations

import argparse
import json
import shutil
import tempfile
from pathlib import Path

import jax

from chipbench import drive, run, spec
from chipbench.trace_reduce import reduce_trace


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    run.compile_cache()
    from accl_tpu.device.tpu import tpu_world
    devs = run.devices(cell.chips)
    accls = tpu_world(cell.chips)
    r = drive.Run(accls, cell, args.seed)
    r.warm()
    run.TRACE_DIR.mkdir(parents=True, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=run.TRACE_DIR)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    span = drive.spans(True)
    with span("chipbench.window"):
        r.run(seconds=args.seconds, span=span)
    jax.profiler.stop_trace()
    src = next(Path(tmp).glob("**/*.xplane.pb"))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, args.out)
    shutil.rmtree(tmp)
    red = reduce_trace(args.out, [d.id for d in devs])
    print(json.dumps({"window_s": red.window_s, "busy_s": red.busy_s,
                      "collective_s": red.collective_s,
                      "chips": [c.plane for c in red.chips],
                      "device_ops": red.top("op_s"),
                      "idle_gaps": red.top("gap_s")}))
    r.results()
    for a in accls:
        a.deinit()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
