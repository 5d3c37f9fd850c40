"""Find a cell's parts by name: BENCHMARK.json, then the files it names.

Everything a cell needs lives in files of its own: the configuration in
``bench/configs/<config>.json``, the equations of its architecture in the
one file of ``bench/arch/`` whose ``MODEL_TYPES`` holds the configuration's
published ``model_type`` (see ``architecture``), the traffic mix in
``bench/traffic/<traffic>.json``, each metric's reader in
``bench/metrics/<metric>.py`` (or one file for the metrics that differ only
in their last ``.part``) and the cell's limits of the comparison in
``bench/limits/<workload>.json``.  A later cell, mix or metric is a new
file plus a new entry in BENCHMARK.json, and a new architecture is a new
file in ``bench/arch/`` (with its reference block in
``bench/reference/<name>.py``); nothing here changes.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
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
    arch: ModuleType
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
    arch = architecture(config.get("model_type"), root)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    e2e = [Metric(m["name"], m["unit"])
           for m in bench["end_to_end"] if _applies(m, name)]
    layer = [Metric(m["name"], m["unit"])
             for m in bench["per_layer"] if _applies(m, name)]
    return Cell(name, w["config"], w["traffic"], int(w["chips"]), config,
                arch, traffic, e2e, layer)


def architecture(model_type: Optional[str], root: Path = ROOT) -> ModuleType:
    """The one file ``bench/arch/<name>.py`` whose ``MODEL_TYPES`` holds the
    configuration's ``model_type``; ValueError where none or several do.

    Such a file provides ``MODEL_TYPES``; ``shapes(config)``: hashable sizes
    with at least ``vocab`` and ``dtype``, refusing any key its equations do
    not cover; ``model_config(name, config)`` and ``make_params(seed,
    shapes)``: the program's ``ModelConfig`` and parameter tree, made on the
    device in one jitted call; ``served_gaps(seed, shapes, requests,
    control=False)``: the float32 reference's gaps, or the fp8 control's;
    ``prefill_flops(shapes, start, end)`` and ``decode_work(shapes, steps,
    ctx_sum, tokens)``: the work ``mfu`` and ``paged_decode_roofline`` read."""
    files = sorted((root / "bench" / "arch").glob("*.py"))
    claims = [p for p in files if model_type in _load(p).MODEL_TYPES]
    if len(claims) != 1:
        raise ValueError(
            f"model_type {model_type!r} is claimed by {len(claims)} of "
            f"{[str(p) for p in files]}: {[p.name for p in claims]}; "
            "exactly one bench/arch/ file has to claim it")
    return _load(claims[0])


@functools.lru_cache(maxsize=None)
def _load(path: Path) -> ModuleType:
    """The Python file at ``path``, run once a process (so an architecture's
    programs compile once) and registered by its path, as dataclasses need."""
    name = "bench_file_" + re.sub(r"\W", "_", str(path))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


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
    return _load(path).read
