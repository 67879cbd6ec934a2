"""The benchmark's files: everything ``BENCHMARK.json`` names is found by
its name, names and units keep to their characters, new cells, configs
and metrics are picked up as new files, and a run without a TPU stops
before it prints a result."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import tiny
from bench import sim as sim_mod
from bench.registry import Registry

REPO = tiny.REPO
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}


def test_top_level_keys_and_limits():
    assert set(BENCH) == TOP_KEYS
    assert 1 <= BENCH["run_seconds"] <= 51
    assert all(re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p)
               for p in BENCH["paths"])
    assert len(json.dumps(BENCH)) < 64 * 1024
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("kind", ["configs", "workloads", "end_to_end",
                                  "per_layer"])
def test_names_and_units(kind):
    names = [e["name"] for e in BENCH[kind]]
    assert len(set(names)) == len(names)
    for e in BENCH[kind]:
        assert NAME.fullmatch(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.fullmatch(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for key in ("config", "traffic"):
            if key in e:
                assert NAME.fullmatch(e[key])
        for key in e.get("reduced", []):
            assert NAME.fullmatch(key)
            assert not key.endswith(("_dim", "_rank"))


def test_every_name_is_found():
    reg = Registry(REPO)
    for c in BENCH["configs"]:
        cfg = reg.config(c["name"])
        assert cfg.data["name"] == c["name"]
        spec, stdp = cfg.build(1, True)
        assert spec.n_neurons == sum(
            p["n"] for p in cfg.data["network"]["populations"])
        assert set(c["reduced"]) <= set(cfg.data["reduced"])
    for w in BENCH["workloads"]:
        wl = reg.workload(w["name"])
        assert wl["limits"]["edges_differ"] == 0
        assert reg.traffic(w["traffic"])["entry"] in ("engine.run",
                                                      "distributed")
        assert {"setup_s"} < {m["name"] for m in reg.end_to_end(w["name"])}
        assert reg.per_layer(w["name"])
    for m in BENCH["per_layer"]:
        assert callable(reg.reader(m["name"]))
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_new_files_are_picked_up_without_edits(tmp_path):
    """A config, a cell and a metric added as files plus entries: no file
    that was there changes."""
    root = tiny.make_root(tmp_path)
    before = {p: p.read_bytes() for p in (REPO / "bench").rglob("*.py")}
    (root / "bench" / "metrics" / "calls_traced.py").write_text(
        "def read(ctx):\n    return float(ctx.steps)\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append(dict(
        name="calls_traced", unit="steps", better="higher",
        source="host_clock", layer="device", moves="bio_s_per_s",
        workloads=["hpc-static-tiny"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    reg = Registry(root)
    assert reg.config("hpc_tiny").data["scale"] == 0.02
    assert reg.config("hpc_tiny").build(3, False)[0].n_neurons == 225
    assert reg.workload("marmoset-tiny")["chips"] == 4
    assert "calls_traced" in [m["name"] for m in
                              reg.per_layer("hpc-static-tiny")]
    assert "calls_traced" not in [m["name"] for m in
                                  reg.per_layer("hpc-stdp")]
    assert reg.reader("calls_traced")(type("C", (), {"steps": 30})) == 30.0
    after = {p: p.read_bytes() for p in (REPO / "bench").rglob("*.py")}
    assert before == after


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**63 + 11])
def test_seeds_of_any_size(seed):
    s = sim_mod.Seeds.from_seed(seed)
    assert s == sim_mod.Seeds.from_seed(seed)
    assert all(0 <= v < 2**32 for v in (s.network, s.key, s.v_init))


def _bench_cmd(cwd):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable] + BENCH["command"][1:]
        + ["--workload", "hpc-static", "--seed", "1", "--seconds", "1",
           "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    r = _bench_cmd(REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"metrics"' not in r.stdout


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths has
    no program to measure."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _bench_cmd(tmp_path)
    assert r.returncode != 0
    assert '"metrics"' not in r.stdout
