"""Find the benchmark's parts by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix, and
the metrics; the files behind those names live under the benchmark's own
directory:

* ``configs/<config>.json`` — a deployment's sizes and parameters
  (the path is the configuration's ``file`` entry);
* ``traffic/<traffic>.json`` — a traffic mix's parameters; its ``kind``
  names the module that drives it;
* ``kinds/<kind>.py`` — one kind of traffic: its generator, its driver
  of the timed path (``run``), and its comparison with the plain
  reference (``PRECISION``, ``inputs``, ``reference``, ``gaps``), which
  the control (``control.py``) uses as a run does;
* ``popularity/<law>.py`` — a tenant popularity law (``draw``), named by
  a mix;
* ``end_to_end/<metric>.py`` — one end-to-end metric's reader, a module
  with ``read(outcome) -> float``;
* ``metrics/<metric>.py`` — one per-layer metric's reader, a module with
  ``read(readings) -> float | None``;
* ``limits/<cell>.json`` — the limits of the numbers that decide
  ``correct`` in that cell.

A later cell, mix, kind, law or metric is a new file and a new entry:
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from types import ModuleType
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

_MODULES: Dict[str, ModuleType] = {}


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load(bench_dir: str, part: str, name: str) -> ModuleType:
    """The module ``<bench_dir>/<part>/<name>.py``, loaded once by path (a
    name may hold dots)."""
    path = os.path.join(bench_dir, part, name + ".py")
    if path not in _MODULES:
        if not os.path.isfile(path):
            raise KeyError(f"no {part} named {name!r} ({path})")
        spec = importlib.util.spec_from_file_location(
            "tpubench_" + re.sub(r"\W", "_", os.path.relpath(
                path, os.path.dirname(bench_dir))), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


class Registry:
    """The benchmark rooted at ``root`` (the directory that holds
    ``BENCHMARK.json``)."""

    def __init__(self, root: str = ROOT):
        self.root = os.path.abspath(root)
        self.bench_dir = os.path.join(self.root, os.path.basename(BENCH_DIR))
        self.spec = load_json(os.path.join(self.root, "BENCHMARK.json"))

    def cell_names(self) -> List[str]:
        return [w["name"] for w in self.spec["workloads"]]

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {self.cell_names()})")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.bench_dir, "traffic",
                                      name + ".json"))

    def limits(self, cell: str) -> Dict[str, float]:
        return load_json(os.path.join(self.bench_dir, "limits",
                                      cell + ".json"))["limits"]

    def _metrics_for(self, key: str, cell: str) -> List[dict]:
        return [m for m in self.spec[key]
                if cell in m.get("workloads", [cell])]

    def end_to_end(self, cell: str) -> List[dict]:
        return self._metrics_for("end_to_end", cell)

    def per_layer(self, cell: str) -> List[dict]:
        return self._metrics_for("per_layer", cell)

    def kind(self, name: str) -> ModuleType:
        return load(self.bench_dir, "kinds", name)

    def popularity(self, name: str) -> Callable:
        return load(self.bench_dir, "popularity", name).draw

    def end_to_end_reader(self, metric: str) -> Callable:
        return load(self.bench_dir, "end_to_end", metric).read

    def reader(self, metric: str) -> Callable:
        return load(self.bench_dir, "metrics", metric).read


def rehearsal(entry: dict) -> dict:
    """A configuration or traffic entry with its tiny CPU-rehearsal sizes
    (its ``rehearse`` block) laid over it."""
    out = {k: v for k, v in entry.items() if k != "rehearse"}
    out.update(entry.get("rehearse", {}))
    return out
