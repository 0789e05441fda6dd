"""`BENCHMARK.json` and the files it names, found by name.

A later PR adds a configuration, a traffic mix, a cell or a metric by adding
files under one of the manifest's `paths` and appending entries; nothing
here lists them.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType

ROOT = Path(__file__).resolve().parents[1]
DEFAULT = ROOT / "BENCHMARK.json"


class ManifestError(KeyError):
    """A name the manifest does not have, or a file it names is missing."""


class Manifest:
    def __init__(self, path: str | Path = DEFAULT):
        self.path = Path(path)
        self.data = json.loads(self.path.read_text())

    def _named(self, section: str, name: str) -> dict:
        for entry in self.data[section]:
            if entry["name"] == name:
                return entry
        known = [e["name"] for e in self.data[section]]
        raise ManifestError(f"no {section} entry named {name!r}; have {known}")

    def workload(self, name: str) -> dict:
        return self._named("workloads", name)

    def config(self, name: str) -> dict:
        """The configuration as it is run: the entry's file, whole."""
        return json.loads((ROOT / self._named("configs", name)["file"]).read_text())

    def find(self, folder: str, filename: str) -> Path:
        """`<path>/<folder>/<filename>` under the first of `paths` that has it."""
        for base in self.data["paths"]:
            candidate = ROOT / base / folder / filename
            if candidate.is_file():
                return candidate
        raise ManifestError(
            f"no {folder}/{filename} under any of {self.data['paths']}"
        )

    def json(self, folder: str, name: str) -> dict:
        return json.loads(self.find(folder, f"{name}.json").read_text())

    def module(self, folder: str, name: str) -> ModuleType:
        """The Python file `<folder>/<name>.py`, loaded by path so that a
        name with a dot or a dash in it is fine."""
        path = self.find(folder, f"{name}.py")
        key = "benchmarks._found." + "".join(
            c if c.isalnum() else "_" for c in f"{path.parent.parent.name}_{folder}_{name}"
        )
        if key in sys.modules:
            return sys.modules[key]
        spec = importlib.util.spec_from_file_location(key, path)
        module = importlib.util.module_from_spec(spec)
        sys.modules[key] = module
        spec.loader.exec_module(module)
        return module

    def end_to_end_for(self, workload: str) -> list[dict]:
        return [
            m for m in self.data["end_to_end"]
            if workload in m.get("workloads", [workload])
        ]

    def per_layer_for(self, workload: str) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end_for(workload)}
        return [
            m for m in self.data["per_layer"]
            if (workload in m["workloads"] if "workloads" in m else m["moves"] in reported)
        ]
