"""The trace reduction: leaf ops only (a ``while`` that spans its body's
ops is not counted again), busy time as the union of their intervals,
and op classes from the compiled program's HLO text, on small op lists
and on a small trace recorded on a TPU v5e (``data/probe.xplane.pb``, a
jitted scan of gathers and scatters, with its ``data/probe_hlo.txt``)."""

from pathlib import Path

import pytest

import tiny  # noqa: F401  (puts the repository on the path)
from bench import trace as T

DATA = Path(__file__).parent / "data"


def _op(name, opcode, start, dur):
    return T.Op(name=name, opcode=opcode, start_ps=start, dur_ps=dur)


def test_nested_whiles_are_not_leaves():
    ops = sorted([
        _op("while.1", "while", 0, 100),
        _op("fusion.1", "fusion", 0, 10),          # starts with the while
        _op("while.2", "while", 20, 50),           # nested loop
        _op("gather.1", "gather", 20, 30),
        _op("fusion.2", "fusion", 50, 20),         # ends with while.2
        _op("copy.1", "copy", 100, 5),             # touches while.1's end
    ], key=lambda o: (o.start_ps, -o.dur_ps))
    leaves = T.leaves(ops)
    assert [o.name for o in leaves] == ["fusion.1", "gather.1", "fusion.2",
                                        "copy.1"]
    assert T.union_ps(leaves) == 10 + 30 + 20 + 5
    assert T.union_ps(ops) == 105   # with the whiles: the loop's span


def test_union_merges_overlaps():
    ops = [_op("a", "fusion", 0, 10), _op("b", "fusion", 5, 10),
           _op("c", "fusion", 30, 1)]
    assert T.union_ps(ops) == 16


HLO = """HloModule m

%fused_computation.1 (p0: f32[8], p1: s32[8]) -> f32[4] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %scatter.1 = f32[4]{0} scatter(%p0, %p1, %p0), to_apply=%add
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %gather.3 = f32[8]{0} gather(%p0, %p0), offset_dims={}
}

ENTRY %main (a: f32[8]) -> f32[4] {
  %a = f32[8]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%a), kind=kCustom, calls=%fused_computation.1
  %fusion.8 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2
  ROOT %all-reduce.2 = f32[4]{0} all-reduce(%fusion.7), to_apply=%add
}
"""


def test_hlo_classes_of_fusions():
    classes = T.hlo_classes(HLO)
    assert classes["fusion.7"] == "scatter"
    assert classes["fusion.8"] == "gather"
    op = lambda name, opcode: _op(name, opcode, 0, 1)
    assert T.op_class(op("fusion.7", "fusion"), classes) == "scatter"
    assert T.op_class(op("all-reduce-start.2", "all-reduce-start"),
                      classes) == "collective"
    assert T.op_class(op("table_gather.3", "custom-call"),
                      classes) == "gather"
    assert T.op_class(op("add.1", "add"), classes) == "other"


@pytest.fixture(scope="module")
def recorded():
    return T.load(str(DATA / "probe"), (DATA / "probe_hlo.txt").read_text())


def test_recorded_trace(recorded):
    assert recorded.n_devices == 1
    planes = T.read_planes(T.find_xplane(str(DATA / "probe")))
    ops = next(iter(T.device_ops(planes).values()))
    whiles = [o for o in ops if o.opcode == "while"]
    leaves = next(iter(recorded.per_device.values()))
    assert whiles and not [o for o in leaves if o.opcode == "while"]
    # every loop encloses leaf ops, and busy time counts them once
    for w in whiles:
        assert any(w.start_ps <= o.start_ps and o.end_ps <= w.end_ps
                   for o in leaves)
    busy_ps = recorded.busy_s() * 1e12
    assert busy_ps == pytest.approx(T.union_ps(leaves))
    assert busy_ps <= sum(o.dur_ps for o in leaves) + 1
    assert busy_ps < sum(o.dur_ps for o in ops)
    span = max(o.end_ps for o in ops) - min(o.start_ps for o in ops)
    assert busy_ps <= span
    gather, scatter = recorded.class_s("gather"), recorded.class_s("scatter")
    assert gather > 0 and scatter > 0
    assert gather + scatter <= recorded.busy_s() * (1 + 1e-9)
    top = recorded.top_ops(10)
    assert 0 < len(top) <= 10
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)
    assert len(recorded.idle_gaps(10)) <= 10
