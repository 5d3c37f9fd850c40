"""Find a cell's parts by name: BENCHMARK.json, then the files it names.

Everything a cell needs lives in files of its own: the configuration in
``bench/configs/<config>.json``, the traffic mix in
``bench/traffic/<traffic>.json``, each metric's reader in
``bench/metrics/<metric>.py`` (or one file for the metrics that differ only
in their last ``.part``) and the cell's limits of the comparison in
``bench/limits/<workload>.json``.  A later cell, mix or metric is a new
file plus a new entry in BENCHMARK.json; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    e2e: List[Metric]
    layer: List[Metric]


def load_benchmark(root: Path = ROOT) -> Dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(entry: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The workload ``name`` with its configuration, traffic and metrics.
    Raises KeyError for an unknown workload."""
    bench = load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, name)]
    layer = [Metric(m["name"], m["unit"])
             for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, w["config"], w["traffic"], int(w["chips"]), config,
                traffic, e2e, layer)


def load_limits(workload: str, root: Path = ROOT) -> Dict[str, Any]:
    """``bench/limits/<workload>.json``: for each number compared to decide
    ``correct``, its limit and the readings it was set from, and the plan of
    the sample compared.  Without a file every limit is 0."""
    path = root / "bench" / "limits" / f"{workload}.json"
    if not path.exists():
        return {}
    with open(path) as f:
        return json.load(f)


def metric_reader(name: str, root: Path = ROOT) -> Callable[[Any], Optional[float]]:
    """``read(run)`` from ``bench/metrics/<name>.py``, or, where there is no
    such file, from the file named without the last ``.part``: one reader
    serves ``decode_step_ms.chat`` and ``decode_step_ms.batch``.  A reader
    returns None where it finds nothing to read; the metric is then left
    out."""
    metrics = root / "bench" / "metrics"
    path = metrics / f"{name}.py"
    if not path.exists() and "." in name:
        path = metrics / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
