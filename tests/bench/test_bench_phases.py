"""The program's phase scopes and the benchmark's per-phase readers.

- ``hlo_phases`` on a hand-written HLO: the outermost phase component of
  an instruction's ``op_name`` wins, a component that only contains a
  phase name is none, and an instruction without metadata is unphased.
- The compiled ``engine.run`` of the tiny hpc twin with STDP on puts
  every gather and scatter in ``sweep`` or ``stdp`` and the neuron
  update in ``neuron``; the four-device mesh step puts every collective
  in ``exchange``.
- On a small scoped trace recorded on a TPU v5e
  (``data/phases.xplane.pb.gz``, one 10-step call of the tiny twin, with
  ``data/phases_hlo.txt.gz``; made by ``record_phases_trace.py``) the phases and the unphased ops add up to
  the busy time, and the readers report them.
- On the probe recorded before the scopes existed, the five older
  readers read what they always read, and the new ones read nothing.
"""

import dataclasses
import gzip
import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import tiny
from bench import phases as PH
from bench import run as run_mod
from bench import sim as sim_mod
from bench import trace as T
from bench.registry import Registry
from repro.core import engine

DATA = Path(__file__).parent / "data"
NEW = ("sweep_ms_per_step", "neuron_ms_per_step", "stdp_ms_per_step",
       "unphased_ms_per_step")

HLO = """HloModule m

%fused_computation.1 (p0: f32[8], p1: s32[8]) -> f32[4] {
  %p0 = f32[8]{0} parameter(0)
  %neg.1 = f32[8]{0} negate(%p0), metadata={op_name="jit(f)/while/body/neuron/neg"}
  ROOT %scatter.1 = f32[4]{0} scatter(%neg.1, %p1, %p0), to_apply=%add, metadata={op_name="jit(f)/while/body/sweep/scatter-add"}
}

ENTRY %main (a: f32[8]) -> f32[4] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.7 = f32[4]{0} fusion(%a), kind=kCustom, calls=%fused_computation.1, metadata={op_name="jit(f)/while/body/sweep/scatter-add"}
  %gather.2 = f32[8]{0} gather(%a, %a), metadata={op_name="jit(f)/stdp/inner/sweep/gather"}
  %add.3 = f32[8]{0} add(%a, %a), metadata={op_name="jit(f)/jit(sweep)/neuronal/add"}
  %copy.4 = f32[8]{0} copy(%a)
  ROOT %all-gather.5 = f32[4]{0} all-gather(%fusion.7), metadata={op_name="jit(f)/shard_map/exchange/all_gather"}
}
"""


def _op(name, start, dur):
    return T.Op(name=name, opcode="fusion", start_ps=start, dur_ps=dur)


def test_hlo_phases_rules():
    got = PH.hlo_phases(HLO, engine.PHASES)
    assert got["fusion.7"] == "sweep"      # the fusion's own metadata
    assert got["neg.1"] == "neuron"        # a fused instruction's own
    assert got["gather.2"] == "stdp"       # outermost phase component
    assert got["all-gather.5"] == "exchange"
    assert "add.3" not in got              # jit(sweep), neuronal: none
    assert "copy.4" not in got             # no metadata
    assert "a" not in got
    assert PH.hlo_phases(HLO, ()) == {}

    trace = T.TraceSummary(per_device={
        "/device:TPU:0": [_op("fusion.7", 0, 30), _op("gather.2", 30, 20),
                          _op("copy.4", 50, 5), _op("add.3", 55, 1)],
        "/device:TPU:1": [_op("fusion.7", 0, 10), _op("gather.2", 10, 20),
                          _op("all-gather.5", 30, 4)]},
        classes={}, host=[])
    s = lambda name: PH.phase_s(trace, got, name) * 1e12
    assert s("sweep") == pytest.approx(20)
    assert s("stdp") == pytest.approx(20)
    assert s("exchange") == pytest.approx(2)
    assert s("neuron") == 0
    assert s(None) == pytest.approx(3)
    assert sum(s(p) for p in engine.PHASES + (None,)) == pytest.approx(
        trace.busy_s() * 1e12)


def test_phase_names_are_checked():
    assert engine.PHASES == ("sweep", "neuron", "stdp", "exchange")
    with pytest.raises(ValueError, match="unknown phase"):
        engine.phase("ring")


# ---- the compiled step ------------------------------------------------------

_SECTION = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?(?P<n>[\w.\-]+) = .*? "
                    r"(?P<op>[a-z][a-z0-9-]*)\(")


def _instructions(text: str):
    """``(name, opcode, op_name, source file)`` of every instruction of an
    HLO module text; the source file is that of the instruction's
    innermost stack frame (None without one)."""
    tables, cur = {}, None
    for line in text.splitlines():
        if _SECTION.match(line):
            cur = tables.setdefault(line, {})
        elif not line.strip():
            cur = None
        elif cur is not None:
            k, v = line.split(" ", 1)
            cur[int(k)] = v
    files = {k: v.strip('"') for k, v in tables["FileNames"].items()}
    loc_file = {k: int(re.search(r"file_name_id=(\d+)", v).group(1))
                for k, v in tables["FileLocations"].items()}
    frame_loc = {k: int(re.search(r"file_location_id=(\d+)", v).group(1))
                 for k, v in tables["StackFrames"].items()}
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        f = re.search(r"stack_frame_id=(\d+)", line)
        src = files[loc_file[frame_loc[int(f.group(1))]]] if f else None
        o = PH._OP_NAME.search(line)
        out.append((m.group("n"), m.group("op"), o and o.group("path"), src))
    return out


def _in_shared_helper(path: str, phase: str) -> bool:
    """Whether ``path`` runs through a ``jax.jit`` helper (``jit(_take)``)
    below ``phase``: JAX lowers each distinct call of such a helper once
    per module, so an identical call in another phase carries this one's
    scope."""
    parts = path.split("/")
    return phase in parts and parts[parts.index(phase) + 1].startswith(
        "jit(")


@pytest.fixture(scope="module")
def tiny_stdp(tmp_path_factory):
    """The tiny hpc twin with STDP on, placed as the benchmark places it."""
    reg = Registry(tiny.make_root(tmp_path_factory.mktemp("root")))
    wl = reg.workload("hpc-stdp-tiny")
    sim = sim_mod.make_sim(reg.config(wl["config"]),
                           reg.traffic(wl["traffic"]),
                           sim_mod.Seeds.from_seed(2**32 + 5),
                           jax.devices()[:1])
    sim.spec, sim.stdp = sim.config.build(sim.net_seed, sim.plastic)
    sim._build()
    sim.v0 = sim_mod.v_init(sim.config.data, sim.seeds.v_init)
    return sim, sim._place()


@pytest.mark.parametrize("sweep", ["flat", "bucketed"])
def test_step_phases(tiny_stdp, sweep):
    sim, state = tiny_stdp
    sim.cfg = dataclasses.replace(sim.cfg, sweep=sweep)
    text = jax.jit(sim._call).lower(state, sim.operands).compile().as_text()
    phase_of = PH.hlo_phases(text, engine.PHASES)
    instrs = _instructions(text)
    edge_ops = [(n, op) for n, op, _, _ in instrs
                if op in ("gather", "scatter")]
    assert edge_ops
    for n, op in edge_ops:
        assert phase_of.get(n) in ("sweep", "stdp"), (n, op)
    assert {phase_of.get(n) for n, _ in edge_ops} == {"sweep", "stdp"}
    neuron_ops = [(n, path) for n, _, path, src in instrs
                  if src and src.endswith(("/neuron_models.py", "/snn.py"))]
    assert neuron_ops
    elsewhere = [(n, path) for n, path in neuron_ops
                 if phase_of.get(n) != "neuron"]
    for n, path in elsewhere:
        assert _in_shared_helper(path, phase_of.get(n)), (n, path)
    assert len(elsewhere) < len(neuron_ops) // 10


MESH_CODE = r"""
import json, sys, tempfile
sys.path.insert(0, sys.argv[1])
import tiny
import jax
from bench import sim as sim_mod, phases as PH, trace as T
from bench.registry import Registry
from repro.core import engine

reg = Registry(tiny.make_root(tempfile.mkdtemp()))
wl = reg.workload("marmoset-tiny")
sim = sim_mod.make_sim(reg.config(wl["config"]), reg.traffic(wl["traffic"]),
                       sim_mod.Seeds.from_seed(2**32 + 5), jax.devices())
sim.setup()
text = sim.hlo_text()
phase_of = PH.hlo_phases(text, engine.PHASES)
out = []
for line in text.splitlines():
    m = PH._NAME.match(line)
    op = T._INSTR.match(line)
    if m and op:
        base = op.group("op").removesuffix("-start").removesuffix("-done")
        if base in T.COLLECTIVES:
            out.append([m.group("n"), phase_of.get(m.group("n"))])
print(json.dumps(out))
"""


def test_mesh_collectives_in_exchange():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", MESH_CODE,
                        os.path.dirname(tiny.__file__)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    collectives = json.loads(r.stdout.strip().splitlines()[-1])
    assert collectives
    for name, phase in collectives:
        assert phase == "exchange", name


# ---- the recorded traces ----------------------------------------------------

@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    trace_dir = tmp_path_factory.mktemp("phases")
    with gzip.open(DATA / "phases.xplane.pb.gz", "rb") as fin, \
            open(trace_dir / "phases.xplane.pb", "wb") as fout:
        shutil.copyfileobj(fin, fout)
    with gzip.open(DATA / "phases_hlo.txt.gz", "rt") as fh:
        hlo = fh.read()
    return hlo, T.load(str(trace_dir), hlo)


def test_recorded_scoped_trace(scoped):
    hlo, summary = scoped
    assert summary.n_devices == 1
    phase_of = PH.hlo_phases(hlo, engine.PHASES)
    got = {p: PH.phase_s(summary, phase_of, p) for p in engine.PHASES}
    assert got["sweep"] > 0 and got["neuron"] > 0 and got["stdp"] > 0
    assert got["exchange"] == 0
    total = sum(got.values()) + PH.phase_s(summary, phase_of, None)
    assert total == pytest.approx(summary.busy_s(), rel=1e-9)


def _ctx(summary, steps, window_s):
    return run_mod.ReadContext(times={"build_s": 1.5, "compile_s": 0.25},
                               trace=summary, steps=steps, window_s=window_s)


def _root_with_hlo(tmp_path, hlo: str, cell: str = "cell") -> Registry:
    root = tiny.make_root(tmp_path)
    d = root / ".bench_trace" / cell
    d.mkdir(parents=True)
    (d / "hlo.txt").write_text(hlo)
    return Registry(root)


def test_readers_on_recorded_scoped_trace(scoped, tmp_path):
    hlo, summary = scoped
    reg = _root_with_hlo(tmp_path, hlo)
    ctx = _ctx(summary, steps=10, window_s=1.0)
    got = {m: reg.reader(m)(ctx) for m in NEW}
    assert all(v is not None for v in got.values()), got
    assert got["sweep_ms_per_step"] > 0 and got["stdp_ms_per_step"] > 0
    assert got["unphased_ms_per_step"] >= 0
    assert sum(got.values()) == pytest.approx(
        1e3 * summary.busy_s() / 10, rel=1e-9)
    # a stale trace directory of another program is passed over
    stale = tmp_path / ".bench_trace" / "other" / "hlo.txt"
    stale.parent.mkdir()
    stale.write_text(HLO)
    assert reg.reader("sweep_ms_per_step")(ctx) == got["sweep_ms_per_step"]
    shutil.rmtree(tmp_path / ".bench_trace" / "cell")
    assert reg.reader("sweep_ms_per_step")(ctx) is None


@pytest.fixture(scope="module")
def probe():
    hlo = (DATA / "probe_hlo.txt").read_text()
    return hlo, T.load(str(DATA / "probe"), hlo)


# what the five older readers read on the probe, on the tree before the
# phase scopes
PROBE_READINGS = {
    "build_s": 1.5,
    "compile_s": 0.25,
    "device_idle_share": 11.427737500000013,
    "xla_gather_ms_per_step": 0.35062796874999996,
    "xla_scatter_ms_per_step": 0.75539328125,
}


def test_older_readers_unchanged_and_new_silent_on_probe(probe, tmp_path):
    """The probe's program names no phase, as a checkout from before the
    scopes: the new readers read nothing and do not raise."""
    hlo, summary = probe
    reg = _root_with_hlo(tmp_path, hlo)
    ctx = _ctx(summary, steps=8, window_s=0.01)
    assert {m: reg.reader(m)(ctx) for m in PROBE_READINGS} == PROBE_READINGS
    assert {m: reg.reader(m)(ctx) for m in NEW} == dict.fromkeys(NEW)
