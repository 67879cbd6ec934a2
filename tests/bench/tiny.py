"""A benchmark root with test-only configurations at a size a CPU test can
hold: the committed benchmark files plus tiny twins, added as new files
and entries only.

``make_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp``
and adds the configurations ``hpc_tiny`` (the committed hpc_benchmark
table with a fifth of its neurons and a tenth of its indegrees: 225
neurons, 253,125 synapses) and ``marmoset_tiny`` (the program's marmoset
scenario at scale 0.001, 1,000 neurons, for the four-device mesh path),
with one cell per committed cell, held to that cell's limits.
"""

from __future__ import annotations

import dataclasses
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (str(REPO), str(REPO / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# tiny cell -> (config, traffic, chips, the committed cell whose limits
# it is held to)
CELLS = {"hpc-stdp-tiny": ("hpc_tiny", "plastic_10step", 1, "hpc-stdp"),
         "hpc-static-tiny": ("hpc_tiny", "static_10step", 1, "hpc-static"),
         "marmoset-tiny": ("marmoset_tiny", "mesh2x2_50step", 4,
                           "hpc-static")}


def hpc_tiny(data: dict) -> dict:
    """The committed hpc_benchmark configuration, cut for a CPU test."""
    data = json.loads(json.dumps(data))
    net = data["network"]
    for p in net["populations"]:
        p["n"] //= 5
    for p in net["projections"]:
        p["indegree"] //= 10
    net["areas"][0]["n_neurons"] = sum(p["n"] for p in net["populations"])
    data.update(name="hpc_tiny", scale=data["scale"] / 5)
    return data


def describe(spec) -> dict:
    """The network table of a program ``NetworkSpec`` (seed and area
    positions left out)."""
    return dict(
        areas=[dict(name=a.name, n_neurons=int(a.n_neurons),
                    mem_per_neuron=float(a.mem_per_neuron))
               for a in spec.areas],
        populations=[dict(name=p.name, area=int(p.area), group=int(p.group),
                          n=int(p.n), ext_rate_hz=float(p.ext_rate_hz),
                          ext_weight=float(p.ext_weight))
                     for p in spec.populations],
        projections=[dataclasses.asdict(pr) for pr in spec.projections],
        groups=[dataclasses.asdict(g) for g in spec.groups],
        max_delay=int(spec.max_delay), connectivity=spec.connectivity)


def marmoset_tiny() -> dict:
    from repro.core import models
    spec = dataclasses.replace(models.marmoset(scale=0.001, seed=7),
                               connectivity="procedural")
    return dict(name="marmoset_tiny", dt_ms=models.DT_MS,
                precision="float32", network_seed=7,
                v_init=dict(mean_mV=-57.5, std_mV=5.0),
                network=describe(spec))


def make_root(tmp) -> Path:
    root = Path(tmp)
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg_dir = root / "bench" / "configs"
    hpc = json.loads((cfg_dir / "hpc_benchmark.json").read_text())
    for data in (hpc_tiny(hpc), marmoset_tiny()):
        name = data["name"]
        (cfg_dir / f"{name}.json").write_text(json.dumps(data, indent=1))
        bench["configs"].append(dict(
            name=name, source="test-only", file=f"bench/configs/{name}.json",
            reduced=[], why="test-only twin at a size a CPU test holds"))
    wl_dir = root / "bench" / "workloads"
    for cell, (config, traffic, chips, like) in CELLS.items():
        shutil.copy(wl_dir / f"{like}.json", wl_dir / f"{cell}.json")
        bench["workloads"].append(dict(name=cell, config=config,
                                       traffic=traffic, chips=chips,
                                       why="test-only"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + list(CELLS)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
