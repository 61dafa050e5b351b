"""Finds each piece of a cell by the name ``BENCHMARK.json`` gives it.

A cell names a configuration and a traffic mix; everything that belongs
to one of them, or to one metric, sits in a file of its own:

- ``BENCHMARK.json`` (root): the cells, and which metrics each reports;
- the configuration file that ``BENCHMARK.json`` names for it;
- ``traffic/<traffic>.json``;
- ``limits/<cell>.json``: the limits that decide ``correct``;
- ``families/<family>.py``: the reference and the counts;
- ``metrics/<metric>.py``: one reader per metric, ``read(run)``;
- ``peaks.json``: the chip's peaks, keyed by ``device_kind``.

Adding a cell adds files and entries; no file here changes.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def _json(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> Dict[str, Any]:
    return _json(root / "BENCHMARK.json")


def cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: Dict[str, Any], name: str,
           root: Path = ROOT) -> Dict[str, Any]:
    for c in bench["configs"]:
        if c["name"] == name:
            return _json(root / c["file"])
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> Dict[str, Any]:
    return dict(_json(HERE / "traffic" / f"{name}.json"), name=name)


def limits(cell_name: str) -> Dict[str, Any]:
    return _json(HERE / "limits" / f"{cell_name}.json")


def family(name: str):
    return importlib.import_module(f"benchmarks.chip.families.{name}")


def peaks(device_kind: str) -> Dict[str, Any]:
    table = _json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table has {sorted(table)}")
    return table[device_kind]


def metric_module(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.metrics." + name.replace(".", "_"), path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str) -> Callable[[Any], Any]:
    return metric_module(name).read


def cell_metrics(bench: Dict[str, Any], cell_name: str, kind: str
                 ) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics the cell reports: those
    that list it, and those that list no cells at all."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]
