"""Find a cell and everything it names, by name, in files of their own.

``BENCHMARK.json`` at the checkout's root lists configurations, cells and
metrics. Everything a cell needs is a file found by its name:

* its configuration: the file its ``configs`` entry names;
* its traffic mix: ``traffic/<traffic>.json``, data that ``traffic.py``
  turns into calls;
* each library call the mix names: ``ops/<op>.py`` (how to issue it, and
  what it should produce);
* the buffer sizes of its configuration: ``buffers/<rule>.py``, the rule
  the configuration's ``buffers`` key names;
* each metric it reports, end-to-end or per-layer: ``metrics/<name>.py``,
  whose ``read(reading)`` returns the number, or None where the run has
  nothing for it to read. A metric named ``<base>.<tag>`` (one quantity,
  split by the end-to-end metric its cells report) falls back to
  ``metrics/<base>.py`` where it has no file of its own. The per-layer
  metrics that move one end-to-end metric carry one tag, so a misspelt tag,
  which would fall back unseen, is refused.

Adding a cell, a configuration, a call or a metric is adding such files and
entries; no code here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple       # the BENCHMARK.json entries this cell reports
    per_layer: tuple


def load_benchmark(root: Path | None = None) -> dict:
    with open((root or ROOT) / "BENCHMARK.json") as f:
        return json.load(f)


def _for_cell(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    # a metric without a cell list is reported wherever what it moves is
    return metric.get("moves", metric["name"]) in reported


def check_tags(bench: dict) -> None:
    """Refuse per-layer ``<base>.<tag>`` names whose tags differ among the
    metrics that move one end-to-end metric."""
    tags: dict = {}
    for m in bench["per_layer"]:
        _, dot, tag = m["name"].partition(".")
        if dot:
            tags.setdefault(m["moves"], set()).add(tag)
    mixed = {k: sorted(v) for k, v in tags.items() if len(v) > 1}
    if mixed:
        raise ValueError(f"per-layer metrics that move one end-to-end "
                         f"metric carry one tag: {mixed}")


def find_cell(name: str, bench: dict | None = None, root: Path | None = None,
              home: Path | None = None) -> Cell:
    root, home = root or ROOT, home or HERE
    bench = load_benchmark(root) if bench is None else bench
    check_tags(bench)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / entry["file"]) as f:
        config = json.load(f)
    with open(home / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = tuple(m for m in bench["end_to_end"]
                if "workloads" not in m or name in m["workloads"])
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in bench["per_layer"]
                      if _for_cell(m, name, reported))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


_loaded: dict = {}


def module(kind: str, name: str, home: Path | None = None):
    """The module ``<kind>/<name>.py`` (``ops``, ``buffers`` or
    ``metrics``), loaded once."""
    path = (home or HERE) / kind / f"{name}.py"
    if path not in _loaded:
        if not path.exists():
            raise KeyError(f"no {kind} file {name!r}: {path} is missing")
        spec = importlib.util.spec_from_file_location(
            f"chipbench.{kind}.{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def reader(metric: str, home: Path | None = None):
    """The ``read(reading)`` function of ``metrics/<metric>.py``, or, where
    that file is missing, of ``metrics/<base>.py`` for a metric named
    ``<base>.<tag>``."""
    if not ((home or HERE) / "metrics" / f"{metric}.py").exists():
        metric = metric.partition(".")[0]
    return module("metrics", metric, home).read
