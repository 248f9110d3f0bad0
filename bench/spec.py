"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything is found by name, so a later change adds a cell, a mix or a
metric by adding files and entries and edits none:

* configuration ``<c>``: the ``file`` its entry names
  (``bench/configs/<c>.json``); its ``reference`` key names its plain
  reference, ``bench/reference/<r>.py``;
* traffic mix ``<t>``: ``bench/traffic/<t>.json``, read by
  ``bench/generator.py``; its ``entry`` names the module that drives it,
  ``bench/drive_<e>.py``, and its ``content`` kind the module that makes
  its objects, ``bench/content/<k>.py``;
* end-to-end metric ``<m>``: ``bench/end_to_end/<m>.py``;
* per-layer metric ``<m>``: ``bench/layer_metrics/<m>.py``.

A metric's module defines ``read(ctx)``, which returns a number, or
None where the run holds nothing for it to read.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Metric:
    name: str
    unit: str
    better: str
    read: Callable


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Metric] = field(default_factory=list)
    per_layer: List[Metric] = field(default_factory=list)


def load_benchmark(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_reader(path: Path) -> Callable:
    """The ``read`` function of a metric module, loaded by file path
    (metric names may hold dots)."""
    mod_name = "bench_metric_" + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def module(package: str, name: str, prefix: str = ""):
    """``bench.<package>.<prefix><name>``, the module a name in the data
    stands for (``package`` "" for ``bench`` itself)."""
    if not name.isidentifier():
        raise ValueError(f"{name!r} cannot name a module")
    parts = ["bench"] + ([package] if package else []) + [prefix + name]
    return importlib.import_module(".".join(parts))


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(bench: Dict, cell_name: str, root: Path = ROOT) -> Cell:
    """The cell named ``cell_name`` with its configuration, traffic mix
    and the readers of the metrics it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell_name not in cells:
        raise KeyError(f"no workload {cell_name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[cell_name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf = configs[w["config"]]
    cell = Cell(name=cell_name, chips=int(w["chips"]),
                config=_load_json(root / conf["file"]),
                traffic=_load_json(root / "bench" / "traffic"
                                   / f"{w['traffic']}.json"))
    for m in bench["end_to_end"]:
        if _applies(m, cell_name):
            cell.end_to_end.append(Metric(
                m["name"], m["unit"], m["better"],
                load_reader(root / "bench" / "end_to_end"
                            / f"{m['name']}.py")))
    for m in bench["per_layer"]:
        if _applies(m, cell_name):
            cell.per_layer.append(Metric(
                m["name"], m["unit"], m["better"],
                load_reader(root / "bench" / "layer_metrics"
                            / f"{m['name']}.py")))
    return cell


def peaks_for(device_kind: str, root: Path = ROOT) -> Dict:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    table = _load_json(root / "bench" / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json (have {sorted(table)})")
    return table[device_kind]


def kernel_names(root: Path = ROOT) -> Dict[str, Dict[str, str]]:
    """Kernel -> how its operations are found in a device trace: the
    program (``module``) and operation (``op``) substrings of
    ``bench/kernels.json``."""
    return _load_json(root / "bench" / "kernels.json")
