"""The readings a cell's comparison limit is set from, on the chip.

``python -m chipbench.calibrate --workload <name> --seeds 12 --controls 3``

In one process, for each seed: buffers from the seed, warm-up, a short
window through the cell's own traffic at its own size, and the comparison
with the reference: the program's reading. For the first ``--controls``
seeds it also reads the control: the reference summed in the next type
below the configuration's, put where the program's results go, compared the
same way. A limit lies above every program reading and below every control
reading (PERF.md gives both). Prints one JSON line per reading and a
summary line.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from . import check, drive, run, spec


def readings(cell: spec.Cell, accls, seeds, controls: int, seconds: float,
             emit=print) -> dict:
    prog, ctl = [], []
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        r = drive.Run(accls, cell, seed)
        r.warm()
        win = r.run(seconds=seconds)
        failed = r.failed
        results = r.results()
        ref = check.Reference(cell, seed, len(accls))
        value = check.result_err(ref, results)
        c = (check.result_err(ref, check.control_results(ref, list(results)))
             if i < controls else None)
        prog.append(value)
        if c is not None:
            ctl.append(c)
        emit(json.dumps({"seed": seed, "result_err": value, "control": c,
                         "compared": len(results), "steps": win.steps,
                         "failed": failed,
                         "seconds": time.perf_counter() - t}))
    out = {"program_max": max(prog), "program_median": float(np.median(prog)),
           "control_min": min(ctl) if ctl else None,
           "seeds": len(prog), "controls": len(ctl)}
    emit(json.dumps(out))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m chipbench.calibrate")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    run.compile_cache()
    from accl_tpu.device.tpu import tpu_world
    run.devices(cell.chips)
    accls = tpu_world(cell.chips)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    readings(cell, accls, seeds, args.controls, args.seconds)
    for a in accls:
        a.deinit()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
