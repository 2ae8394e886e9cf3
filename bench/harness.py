"""Finds a cell's parts by the names ``BENCHMARK.json`` gives them.

* configuration ``<c>``: the file its ``configs`` entry names
  (``bench/configs/<c>.json``);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* per-layer metric ``<m>``: the reader ``bench/metrics/<m>.py``, whose
  ``read(run)`` returns a number, or None where it finds nothing to read;
* a configuration's check, the name under its ``"check"`` key: the module
  ``bench/checks/<name>.py``, whose ``check(config, run, seed)`` returns
  ``(verdict, {name: (number, limit)})`` for a finished run and whose
  ``control_numbers(cell, seed, seconds)`` returns ``(numbers, limits)``
  of its control.

A cell reports the end-to-end metrics that list it under ``workloads``
(or list no cells), and the per-layer metrics that list it, or that list
no cells and move one of its end-to-end metrics. Adding a cell, a mix or
a metric is adding files and entries; nothing here names one.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


@dataclass
class Cell:
    name: str
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list          # BENCHMARK.json entries
    per_layer: list           # BENCHMARK.json entries
    root: Path = CHECKOUT     # the checkout its files came from

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def load_benchmark(root: Path = CHECKOUT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _read_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing benchmark file {path}")
    return json.loads(path.read_text())


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = CHECKOUT) -> Cell:
    spec = load_benchmark(root)
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; known: {sorted(by_name)}")
    workload = by_name[name]
    [cfg_entry] = [c for c in spec["configs"]
                   if c["name"] == workload["config"]]
    config = _read_json(root / cfg_entry["file"])
    traffic = _read_json(root / "bench" / "traffic"
                         / f"{workload['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(name, workload, config, traffic, e2e, per_layer, root)


def _load_module(path: Path, kind: str):
    if not path.is_file():
        raise FileNotFoundError(f"missing {kind} {path}")
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind.replace(' ', '_')}_"
        + path.stem.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(module)
    return module


def metric_reader(name: str, root: Path = CHECKOUT):
    """The ``read(run)`` function of per-layer metric ``name``."""
    return _load_module(root / "bench" / "metrics" / f"{name}.py",
                        "metric reader").read


def check_module(name: str, root: Path = CHECKOUT):
    """The module of check ``name``: its ``check`` and
    ``control_numbers``."""
    return _load_module(root / "bench" / "checks" / f"{name}.py", "check")
