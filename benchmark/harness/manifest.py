"""BENCHMARK.json and the files it names, all found by name.

A later PR adds a cell, a configuration, a traffic mix or a per-layer
metric as new files and new entries; nothing here lists them.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

#: The checkout: the directory that holds BENCHMARK.json and benchmark/.
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


class ManifestError(ValueError):
    """BENCHMARK.json or a file it names is missing or malformed."""


def load_json(path: str) -> Dict[str, Any]:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as exc:
        raise ManifestError(f"cannot read {path}: {exc}") from exc


class Manifest:
    """The parsed BENCHMARK.json of one checkout."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.data = load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, self.data["paths"][0])

    # -- entries -----------------------------------------------------------

    def cell(self, name: str) -> Dict[str, Any]:
        for entry in self.data["workloads"]:
            if entry["name"] == name:
                return entry
        raise ManifestError(
            f"no workload {name!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in self.data['workloads']]}")

    def config(self, name: str) -> Dict[str, Any]:
        for entry in self.data["configs"]:
            if entry["name"] == name:
                return load_json(os.path.join(self.root, entry["file"]))
        raise ManifestError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> Dict[str, Any]:
        return load_json(os.path.join(self.bench_dir, "traffic",
                                      f"{name}.json"))

    def limits(self, cell: str) -> Dict[str, float]:
        """The limits of the numbers ``correct`` compares in ``cell``."""
        return load_json(os.path.join(self.bench_dir, "limits",
                                      f"{cell}.json"))["limits"]

    # -- metrics -----------------------------------------------------------

    def _reports(self, metric: Dict[str, Any], cell: str) -> bool:
        cells = metric.get("workloads")
        return cells is None or cell in cells

    def end_to_end(self, cell: str) -> List[Dict[str, Any]]:
        return [m for m in self.data["end_to_end"]
                if self._reports(m, cell)]

    def per_layer(self, cell: str) -> List[Dict[str, Any]]:
        """Per-layer metrics of ``cell``: listed there, or unlisted and
        moving an end-to-end metric that the cell reports."""
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.data["per_layer"]
                if self._reports(m, cell) and m["moves"] in reported]

    def reader(self, metric: str) -> Callable[[Any], Optional[float]]:
        """``read`` of ``benchmark/metrics/<metric>.py``."""
        path = os.path.join(self.bench_dir, "metrics", f"{metric}.py")
        if not os.path.exists(path):
            raise ManifestError(f"metric {metric!r} has no reader {path}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_metric_{metric.replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def driver(kind: str) -> Callable[..., Any]:
    """``run`` of ``benchmark/harness/drivers/<kind>.py`` ('-' reads '_')."""
    module = f"benchmark.harness.drivers.{kind.replace('-', '_')}"
    try:
        return importlib.import_module(module).run
    except ModuleNotFoundError as exc:
        if exc.name != module:
            raise
        raise ManifestError(
            f"traffic kind {kind!r} has no driver {module}") from exc
