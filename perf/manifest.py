"""Reads BENCHMARK.json and the data files a cell names. Everything that
belongs to one configuration, one traffic mix, one cell or one per-layer
metric is a file of its own, found here by the name in BENCHMARK.json; a new
one is a new file plus an appended entry, never an edit."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (its name may hold dots or
    dashes, which an import statement could not spell)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Manifest:
    def __init__(self, root: str = ROOT, perf_dir: Optional[str] = None):
        self.root = root
        self.perf_dir = perf_dir or os.path.join(root, "perf")
        self.data = _load_json(os.path.join(root, "BENCHMARK.json"))

    # -- entries of BENCHMARK.json ---------------------------------------
    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                       f"{[w['name'] for w in self.data['workloads']]}")

    def metrics_for(self, workload: str, group: str) -> List[dict]:
        """The metrics of ``group`` (end_to_end | per_layer) this cell
        reports: those with no ``workloads`` key, or that list the cell."""
        return [m for m in self.data[group]
                if "workloads" not in m or workload in m["workloads"]]

    # -- the data files ----------------------------------------------------
    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return _load_json(os.path.join(self.root, c["file"]))
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return _load_json(os.path.join(self.perf_dir, "traffic",
                                       f"{name}.json"))

    def cell(self, workload: str) -> dict:
        return _load_json(os.path.join(self.perf_dir, "cells",
                                       f"{workload}.json"))

    def peaks(self) -> dict:
        return _load_json(os.path.join(self.perf_dir, "peaks.json"))

    # -- the code files found by name ------------------------------------
    def generator(self, name: str):
        return load_module(
            os.path.join(self.perf_dir, "generators", f"{name}.py"),
            f"perf_generator_{name}")

    def reference(self, name: str):
        return load_module(
            os.path.join(self.perf_dir, "reference", f"{name}.py"),
            f"perf_reference_{name}")

    def entry(self, name: str):
        return load_module(
            os.path.join(self.perf_dir, "entries", f"{name}.py"),
            f"perf_entry_{name}")

    def layer_reader(self, metric: str) -> Callable[[dict], Any]:
        """``read(record)`` of perf/layer_metrics/<base>.py, where base is
        the metric's name up to its first dot: ``pallas_share.train`` and
        ``pallas_share.chat`` are one reader, entered twice in
        BENCHMARK.json because they move different end-to-end metrics."""
        base = metric.split(".", 1)[0]
        module = load_module(
            os.path.join(self.perf_dir, "layer_metrics", f"{base}.py"),
            f"perf_layer_metric_{base}")
        return module.read

    def read_layer_metrics(self, workload: str, record: dict) -> Dict[str, dict]:
        """Every per-layer metric of the cell whose reader finds something
        to read. A reader that returns None is left out of the line."""
        out = {}
        for m in self.metrics_for(workload, "per_layer"):
            value = self.layer_reader(m["name"])(record)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out
