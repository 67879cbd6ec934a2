"""Finds everything the benchmark runs by its name in ``BENCHMARK.json``.

- a configuration: the JSON file its entry names (``file``), whose network
  table ``bench.netspec`` turns into the program's spec;
- a cell (``workloads`` entry): its limits in ``bench/workloads/<cell>.json``;
- a traffic mix: ``bench/traffic/<traffic>.json``, read by ``bench.sim``;
- a per-layer metric: the reader ``bench/metrics/<metric>.py``.

A later change adds a configuration, a cell, a traffic mix or a metric by
adding its files and its entry; nothing here names one of them.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

from bench import netspec

__all__ = ["ROOT", "Config", "Registry"]

ROOT = Path(__file__).resolve().parents[1]


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Config:
    """A configuration: its file's content.

    ``network_seed`` in the file is ``"run"`` where the run's seed draws
    the synapses, or the fixed seed of the configuration's own network
    instance."""

    name: str
    data: dict

    def network_seed(self, seeds) -> int:
        fixed = self.data["network_seed"]
        return int(seeds.network if fixed == "run" else fixed)

    def build(self, network_seed: int, plastic: bool):
        """``(NetworkSpec, STDPParams | None)``."""
        return netspec.load(self.data, network_seed, plastic)


class Registry:
    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        with open(self.root / "BENCHMARK.json") as fh:
            self.bench = json.load(fh)

    def _entry(self, kind: str, name: str) -> dict:
        for e in self.bench[kind]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {kind} entry named {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> dict:
        """The cell's entry, with its ``limits``."""
        e = dict(self._entry("workloads", name))
        with open(self.root / "bench" / "workloads" / f"{name}.json") as fh:
            e["limits"] = json.load(fh)["limits"]
        return e

    def traffic(self, name: str) -> dict:
        with open(self.root / "bench" / "traffic" / f"{name}.json") as fh:
            return json.load(fh)

    def config(self, name: str) -> Config:
        with open(self.root / self._entry("configs", name)["file"]) as fh:
            return Config(name=name, data=json.load(fh))

    def end_to_end(self, cell: str) -> list[dict]:
        return [m for m in self.bench["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> list[dict]:
        return [m for m in self.bench["per_layer"]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """The ``read(ctx)`` function of a per-layer metric."""
        mod = _load_module(self.root / "bench" / "metrics" / f"{metric}.py",
                           f"bench_metric_{metric.replace('.', '_')}")
        return mod.read
