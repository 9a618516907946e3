"""Benchmark harness: message-size sweeps, BASELINE config runners, CSV
aggregation.

Parity with the reference benchmark path (SURVEY.md §3.5):
``test/host/run_test.py`` sweeps message sizes × algorithm and shells the
per-collective benchmark; ``test.py benchmark()`` times chained async
calls; ``elaborate_csv.py`` aggregates the CSVs. Here:

* :mod:`benchmarks.timing` — chained-iteration slope timing (robust to
  async dispatch and host fetch latency).
* :mod:`benchmarks.sweep` — per-collective size sweeps over a jax mesh,
  CSV rows with bus bandwidth + per-op latency.
* :mod:`benchmarks.configs` — the five BASELINE.json configurations.
* :mod:`benchmarks.elaborate` — CSV aggregation (mean/std per cell).

CLI: ``python -m benchmarks --config N [--out DIR]`` or
``python -m benchmarks --sweep allreduce --sizes 1024,1048576``.
"""

from .sweep import sweep_collective, SweepResult
from .elaborate import elaborate
