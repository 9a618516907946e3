"""Record a short chip trace of one cell's window with the program's spans.

``python -m chipbench.tests.record_spans --workload <name> --seconds 0.05
--out <file.xplane.pb>`` runs ``record_trace.py`` with the program's spans
(``accl.*``) armed, as a traced benchmark run arms them by profiling its
window, overwrites the checkout's path in the trace's source locations with
one of the same length, and prints what the span metrics read from the
trace and the chips' idle gaps named by the innermost span among the
benchmark's and the program's: on the thread whose launch the chips wait
for, and on any thread. The committed ``data/*.spans.xplane.pb``
came from this script on a TPU v5 lite host.
"""

from __future__ import annotations

import argparse
import collections
import json
from pathlib import Path

from accl_tpu.tracing import SPANS

from chipbench import run, spans, spec
from chipbench.tests import record_trace

PLACEHOLDER = "/work/tree"


def scrub(path: str) -> None:
    """Overwrite the checkout's path inside the file at ``path`` with a
    placeholder of the same length."""
    root = str(spec.ROOT).encode()
    fill = (PLACEHOLDER.encode() + b"_" * len(root))[:len(root)]
    p = Path(path)
    p.write_bytes(p.read_bytes().replace(root, fill))


def readings(path: str, device_ids) -> dict:
    s = spans.Spans.from_trace(path)
    return {"launch_us": s.median_us("accl.launch."),
            "wait_us": s.median_us("accl.wait"),
            "call_self_us": s.median_self_us("accl.call."),
            "stage_us": s.median_per_call_us("accl.stage."),
            "spans": dict(collections.Counter(x[0] for x in s.spans)),
            "idle_gaps": spans.idle_gaps(path, device_ids),
            "idle_gaps_any_thread": spans.idle_gaps(path, device_ids,
                                                    launcher=False)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--out", required=True)
    args, _ = ap.parse_known_args(argv)
    SPANS.arm()
    try:
        rc = record_trace.main(argv)
    finally:
        SPANS.disarm()
    scrub(args.out)
    cell = spec.find_cell(args.workload)
    ids = [d.id for d in run.devices(cell.chips)]
    print(json.dumps(readings(args.out, ids)))
    return rc


if __name__ == "__main__":
    raise SystemExit(main())
