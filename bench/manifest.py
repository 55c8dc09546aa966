"""``BENCHMARK.json`` and the files it names, resolved by name.

Every configuration, traffic mix, per-layer metric and cell lives in a
file of its own; this module finds each one from its name alone, so a
later change adds a cell by adding files and entries, never by editing
the harness:

  configuration ``<c>``  -> ``bench/configs/<c>.json`` (its ``file``)
  its plain reference    -> ``bench/references/<model>.py``
  traffic mix ``<t>``     -> ``bench/mixes/<t>.json`` (data)
  the loop a mix names   -> ``bench/loops/<loop>.py`` (``Loop``)
  metric ``<m>``          -> ``bench/metrics/<m>.py`` (``read(ctx)``),
                            end-to-end and per-layer alike
  cell ``<w>``            -> ``bench/limits/<w>.json`` (its limits)
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: pathlib.Path, name: str):
    """Import one file by path (metric files carry dots in their names,
    and a test's checkout is not on the path)."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Manifest:
    """One loaded ``BENCHMARK.json`` and the lookups the harness needs."""

    def __init__(self, root: pathlib.Path = ROOT):
        self.root = root
        self.data = _load_json(root / "BENCHMARK.json")
        self.configs = {c["name"]: c for c in self.data["configs"]}
        self.cells = {w["name"]: w for w in self.data["workloads"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError(f"unknown workload {name!r}; known: "
                           f"{sorted(self.cells)}")
        return self.cells[name]

    def config(self, name: str) -> dict:
        return _load_json(self.root / self.configs[name]["file"])

    def mix(self, name: str) -> dict:
        return _load_json(self.root / "bench" / "mixes" / f"{name}.json")

    def loop(self, name: str):
        return _load_module(self.root / "bench" / "loops" / f"{name}.py",
                            f"bench_loop_{name}")

    def limits(self, cell: str) -> dict:
        return _load_json(self.root / "bench" / "limits" / f"{cell}.json")

    def reference(self, model: str):
        return _load_module(self.root / "bench" / "references"
                            / f"{model}.py", f"bench_reference_{model}")

    def metric_reader(self, name: str):
        return _load_module(self.root / "bench" / "metrics" / f"{name}.py",
                            "bench_metric_" + name.replace(".", "_"))

    @staticmethod
    def _applies(metric: dict, cell: str) -> bool:
        return "workloads" not in metric or cell in metric["workloads"]

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.data["end_to_end"]
                if self._applies(m, cell)]

    def per_layer(self, cell: str) -> list[dict]:
        reported = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.data["per_layer"]
                if self._applies(m, cell) and m["moves"] in reported]
