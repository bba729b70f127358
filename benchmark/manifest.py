"""``BENCHMARK.json`` and the files it names, found by name.

* a cell's configuration: the ``file`` of its entry in ``configs``;
* its traffic mix: ``traffic/<traffic>.json``;
* the code that drives that traffic: ``drivers/<driver>.py``, where
  ``driver`` is named in the traffic file;
* each metric: ``metrics/<name>.py``, a reader with ``read(run)`` that
  returns the metric's value, or ``None`` where it finds nothing to read.

A later cell, traffic mix or metric is a new file and a new entry; no
existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    driver: ModuleType
    end_to_end: List[dict]  # manifest entries this cell reports
    per_layer: List[dict]
    readers: Dict[str, ModuleType]  # metric name -> reader module


def load_module(path: Path, prefix: str) -> ModuleType:
    """Import a file as a module of its own."""
    name = prefix + re.sub(r"\W", "_", path.stem)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_manifest(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric with ``workloads`` applies to those cells; without, to
    every cell (end-to-end) or every cell that reports the end-to-end
    metric it moves (per-layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in reported


def resolve(name: str, root: Path = ROOT, bench_dir: Path = BENCH_DIR) -> Cell:
    """Everything one cell needs, by the names in the manifest."""
    root, bench_dir = Path(root), Path(bench_dir)
    man = load_manifest(root)
    cells = {w["name"]: w for w in man["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} (known: {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in man["configs"]}
    with open(root / configs[cell["config"]]["file"]) as f:
        config = json.load(f)
    with open(bench_dir / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)
    driver = load_module(bench_dir / "drivers" / f"{traffic['driver']}.py",
                         "benchmark_driver_")
    e2e = [m for m in man["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    layer = [m for m in man["per_layer"] if _applies(m, name, reported)]
    readers = {
        m["name"]: load_module(bench_dir / "metrics" / f"{m['name']}.py",
                               "benchmark_metric_")
        for m in e2e + layer
    }
    return Cell(name, int(cell["chips"]), config, traffic, driver, e2e,
                layer, readers)
