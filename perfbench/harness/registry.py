"""Find a cell's configuration, traffic mix, driver, reference and per-layer
readers by the names that BENCHMARK.json gives them.

Nothing here knows one cell from another: a later cell, traffic mix or
metric is added as files and entries, with no edit to this module.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
import sys
from types import ModuleType
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)
BENCHMARK_JSON = os.path.join(CHECKOUT, "BENCHMARK.json")

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class RegistryError(LookupError):
    """A name that BENCHMARK.json or a traffic file uses has no file."""


def load_module(path: str, name: str) -> ModuleType:
    """Import the Python file at ``path`` under the module name ``name``."""
    if not os.path.isfile(path):
        raise RegistryError(f"no file {path}")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[name]
        raise
    return module


def _json(path: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise RegistryError(f"no file {path}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


class Registry:
    """Files of the benchmark under ``root`` (``perfbench/`` by default)."""

    def __init__(self, root: str = BENCH_DIR):
        self.root = root

    def _path(self, folder: str, name: str, suffix: str) -> str:
        if not NAME_RE.match(name):
            raise RegistryError(f"{name!r} is not a valid name")
        return os.path.join(self.root, folder, name + suffix)

    def _module(self, folder: str, name: str, suffix: str) -> ModuleType:
        key = f"perfbench_{folder}_{abs(hash(self.root))}_{name}"
        return load_module(self._path(folder, name, suffix), key)

    def config(self, name: str) -> Dict[str, Any]:
        return _json(self._path("configs", name, ".json"))

    def reference(self, config: str) -> ModuleType:
        """The configuration's data and plain reference, beside its file."""
        return self._module("configs", config, "_ref.py")

    def traffic(self, name: str) -> Dict[str, Any]:
        return _json(self._path("traffic", name, ".json"))

    def kind(self, name: str) -> ModuleType:
        """The driver that runs traffic of this ``kind``."""
        return self._module("kinds", name, ".py")

    def metric(self, name: str) -> ModuleType:
        """The reader of one per-layer metric (``read(readout)``):
        ``metrics/<name>.py``, or else the reader that every metric of the
        same stem shares, ``metrics/<stem>.py`` (``device_idle.search`` ->
        ``device_idle.py``)."""
        stem = name.split(".")[0]
        if stem != name and not os.path.isfile(
                self._path("metrics", name, ".py")):
            return self._module("metrics", stem, ".py")
        return self._module("metrics", name, ".py")


def load_benchmark(path: str = BENCHMARK_JSON) -> Dict[str, Any]:
    return _json(path)


def find_cell(bench: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise RegistryError(f"no workload {name!r} in BENCHMARK.json; known: "
                        + ", ".join(c["name"] for c in bench["workloads"]))


def cell_metrics(bench: Dict[str, Any], cell: str,
                 section: str) -> List[Dict[str, Any]]:
    """The metrics of ``section`` (``end_to_end`` or ``per_layer``) that
    ``cell`` reports: those that list it, and those that list no cells
    (then every cell that reports the metric it moves)."""
    if section == "end_to_end":
        return [m for m in bench["end_to_end"]
                if "workloads" not in m or cell in m["workloads"]]
    e2e = {m["name"] for m in cell_metrics(bench, cell, "end_to_end")}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in e2e)]


def name_errors(bench: Dict[str, Any]) -> List[str]:
    """Names and units in BENCHMARK.json that break the allowed
    characters, and names used twice."""
    errors: List[str] = []

    def name(value: Optional[str], where: str) -> None:
        if not isinstance(value, str) or not NAME_RE.match(value):
            errors.append(f"{where}: bad name {value!r}")

    seen: Dict[str, set] = {"configs": set(), "workloads": set(),
                            "metrics": set()}
    for cfg in bench["configs"]:
        name(cfg["name"], "configs")
        for key in cfg.get("reduced", ()):
            name(key, f"configs.{cfg['name']}.reduced")
        if cfg["name"] in seen["configs"]:
            errors.append(f"configs: {cfg['name']} twice")
        seen["configs"].add(cfg["name"])
    for cell in bench["workloads"]:
        for key in ("name", "config", "traffic"):
            name(cell[key], f"workloads.{key}")
        if cell["name"] in seen["workloads"]:
            errors.append(f"workloads: {cell['name']} twice")
        seen["workloads"].add(cell["name"])
    for section in ("end_to_end", "per_layer"):
        for metric in bench[section]:
            name(metric["name"], section)
            if not UNIT_RE.match(metric["unit"]):
                errors.append(f"{section}.{metric['name']}: bad unit "
                              f"{metric['unit']!r}")
            if metric["better"] not in ("lower", "higher"):
                errors.append(f"{section}.{metric['name']}: better must be "
                              "lower or higher")
            if metric["name"] in seen["metrics"]:
                errors.append(f"metrics: {metric['name']} twice")
            seen["metrics"].add(metric["name"])
    return errors
