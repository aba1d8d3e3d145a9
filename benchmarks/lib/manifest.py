"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell or
one per-layer metric is a file of its own under the benchmark's directory:

    workloads/<cell>.json         the cell: configuration, mix, chips, rate,
                                  engine settings
    configs/<config>.json         the sizes as run, with their source, and
                                  the name of its plain reference
    reference/<architecture>.py   the plain float32 forward of that model
    traffic/<mix>.json            parameters of the mix, read by one generator
    drivers/<kind>.py             the loop a kind of mix is driven by
    layer_metrics/<metric>.py     one reader per per-layer metric

so a later PR adds a cell, a mix, a configuration or a metric by adding
files and one manifest entry each, and edits nothing that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


class ManifestError(ValueError):
    """The manifest or one of the files it names is missing or inconsistent."""


def _read_json(path: str) -> Dict[str, Any]:
    if not os.path.isfile(path):
        raise ManifestError(f"no such file: {path}")
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise ManifestError(f"no such file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _modname(kind: str, name: str) -> str:
    return f"_bench_{kind}_" + "".join(c if c.isalnum() else "_" for c in name)


class Bench:
    """One benchmark directory and its manifest."""

    def __init__(self, manifest_path: Optional[str] = None,
                 root: Optional[str] = None):
        self.manifest_path = manifest_path or os.path.join(
            REPO_DIR, "BENCHMARK.json")
        self.manifest = _read_json(self.manifest_path)
        self.root = root or os.path.join(
            os.path.dirname(os.path.abspath(self.manifest_path)),
            self.manifest["paths"][0])

    def path(self, *parts: str) -> str:
        return os.path.join(self.root, *parts)

    # -- manifest entries ------------------------------------------------
    def workload_entry(self, name: str) -> Dict[str, Any]:
        for w in self.manifest["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no workload {name!r} in {self.manifest_path}; it has "
            f"{[w['name'] for w in self.manifest['workloads']]}")

    def config_entry(self, name: str) -> Dict[str, Any]:
        for c in self.manifest["configs"]:
            if c["name"] == name:
                return c
        raise ManifestError(f"no configuration {name!r} in the manifest")

    def metrics_for(self, section: str, workload: str) -> List[Dict[str, Any]]:
        """Entries of ``end_to_end`` or ``per_layer`` that this cell reports:
        those without a ``workloads`` list, and those that list the cell."""
        return [m for m in self.manifest[section]
                if "workloads" not in m or workload in m["workloads"]]

    # -- files -----------------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        entry = self.workload_entry(name)
        cell = _read_json(self.path("workloads", name + ".json"))
        for key in ("config", "traffic", "chips"):
            if cell.get(key) != entry[key]:
                raise ManifestError(
                    f"workloads/{name}.json says {key}={cell.get(key)!r}, "
                    f"the manifest {entry[key]!r}")
        return cell

    def config(self, name: str) -> Dict[str, Any]:
        entry = self.config_entry(name)
        return _read_json(os.path.join(
            os.path.dirname(os.path.abspath(self.manifest_path)),
            entry["file"]))

    def traffic(self, name: str) -> Dict[str, Any]:
        return _read_json(self.path("traffic", name + ".json"))

    def driver(self, kind: str):
        return _load_module(self.path("drivers", kind + ".py"),
                            _modname("driver", kind))

    def reference(self, config_name: str):
        """The plain reference the configuration's file names (a path under
        the benchmark's directory; configurations of one architecture at
        different depths name the same file)."""
        rel = self.config(config_name)["reference"]
        return _load_module(self.path(*rel.split("/")),
                            _modname("reference", rel))

    def reader(self, metric: str):
        """The reader of a per-layer metric: ``layer_metrics/<metric>.py``,
        or, for a metric entered a second time as ``train_<name>`` so that
        it can name another ``moves``, the file of ``<name>``."""
        path = self.path("layer_metrics", metric + ".py")
        if not os.path.isfile(path) and metric.startswith("train_"):
            path = self.path("layer_metrics", metric[len("train_"):] + ".py")
        return _load_module(path, _modname("metric", metric))
