"""Finding a cell's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and
lists the metrics with the cells that report them.  This module turns a
cell's name into those parts, read from files laid out by kind under the
benchmark's directory, so that a later cell, configuration or metric is
only new files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    #: the configuration's file, as it is run
    config: Dict[str, Any]
    traffic_name: str
    #: the traffic mix's parameters
    traffic: Dict[str, Any]
    #: the ``end_to_end`` and ``per_layer`` entries this cell reports
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    #: the benchmark's directory, where the cell's modules are found
    base: Path

    def module(self, kind: str, name: str):
        return load_module(kind, name, self.base)

    def reader(self, metric: str):
        """The reader of a per-layer metric: ``metrics/<metric>.py``, else
        the reader of its family, the name without its last dotted part
        (``device.idle.lm`` is read by ``metrics/device.idle.py``)."""
        return load_module("metrics", reader_name(metric, self.base),
                           self.base)


def read_json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return read_json(root / "BENCHMARK.json")


def load_module(kind: str, name: str, base: Path = BENCH):
    """The module ``<base>/<kind>/<name>.py``.  Names may hold dots (a
    metric's reader is named ``plan.host_ms``), so it is loaded by path."""
    path = base / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r}: {path}")
    mod_name = f"_bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    hit = sys.modules.get(mod_name)
    if hit is not None and getattr(hit, "__file__", None) == str(path):
        return hit
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def reader_name(metric: str, base: Path = BENCH) -> str:
    """The name of the file in ``<base>/metrics`` that reads ``metric``:
    the metric's own name, or the longest of its dotted prefixes that has
    a file."""
    parts = metric.split(".")
    for k in range(len(parts), 0, -1):
        name = ".".join(parts[:k])
        if (base / "metrics" / f"{name}.py").is_file():
            return name
    raise FileNotFoundError(f"no reader for the metric {metric!r} in "
                            f"{base / 'metrics'}")


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its
    configuration, traffic mix and metric entries; the cell's files are
    under ``<root>/bench``."""
    bm = load_benchmark(root)
    base = root / BENCH.name
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(it has {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = read_json(root / configs[w["config"]]["file"])
    traffic = read_json(base / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bm["end_to_end"]
           if name in m.get("workloads", (name,))]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer, base=base)
