"""The chip benchmark: cells of collective traffic on TPU v5e chips.

``python -m chipbench --workload <name> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` (``run.py``). Each cell is a
configuration (``configs/``, its buffers cut by ``buffers/<rule>.py``) under
a traffic mix (``traffic/``, data read by ``traffic.py``); each library call
a mix names is issued by ``ops/<op>.py``, and each metric is read by
``metrics/<name>.py`` or, for ``<base>.<tag>``, ``metrics/<base>.py``;
``spec.py`` finds them all by name. ``data.py`` makes
the seeded payloads, ``drive.py`` drives them through the library,
``check.py`` holds the plain reference, ``peaks.py`` the peaks table and work
counts, ``trace_reduce.py`` the reduction of a profiler trace,
``calibrate.py`` the readings the comparison limits were set from, and
``sets.py`` runs a cell several times for its spread.
"""
