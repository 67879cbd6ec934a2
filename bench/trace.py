"""Reduction of a profiler trace (``.xplane.pb``) to device busy time and
op-class times.

On a TPU the trace holds one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line has one event per executed HLO instruction, named by
its HLO text (``%fusion.125 = f32[...] fusion(...), kind=...,
calls=%fused_computation.2``).  A ``while`` (the scan of steps) or a
``conditional`` is an event too, and it spans the ops it runs: only
leaf events, those that enclose no other event of their line, count.

An op's class comes from the compiled program's HLO text: a fusion is
a scatter if its fused computation (or one it calls) holds a scatter,
else a gather if it holds a gather; a ``table_gather`` custom call is a
gather; collectives are named by their opcode.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["Op", "TraceSummary", "find_xplane", "read_planes",
           "hlo_classes", "load", "COLLECTIVES"]

COLLECTIVES = ("all-gather", "all-reduce", "collective-permute",
               "all-to-all", "reduce-scatter", "collective-broadcast")

_EVENT = re.compile(r"^%(?P<name>[^\s=]+) = .*? (?P<op>[a-z][a-z0-9-]*)\(")
_CALLS = re.compile(r"calls=%?(?P<comp>[\w.\-]+)")
_COMP = re.compile(r"^(?:ENTRY )?%?(?P<comp>[\w.\-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*? "
                    r"(?P<op>[a-z][a-z0-9-]*)\((?P<rest>.*)$")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str        # HLO instruction name, e.g. "fusion.125"
    opcode: str      # "fusion", "custom-call", "while", ...
    start_ps: int
    dur_ps: int

    @property
    def end_ps(self) -> int:
        return self.start_ps + self.dur_ps


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _ps(event, key: str, ns: float) -> int:
    for k, v in event.stats:
        if k == key:
            return int(v)
    return int(round(ns * 1000))


def read_planes(xplane: str) -> list:
    """The planes of a ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return list(ProfileData.from_file(xplane).planes)


def device_ops(planes: list) -> dict[str, list[Op]]:
    """The ``XLA Ops`` events of each TPU plane."""
    out = {}
    for plane in planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        ops = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                m = _EVENT.match(e.name)
                name, opcode = (m.group("name"), m.group("op")) if m else \
                    (e.name.split(" ")[0].lstrip("%"), "?")
                ops.append(Op(name=name, opcode=opcode,
                              start_ps=_ps(e, "device_offset_ps",
                                           e.start_ns),
                              dur_ps=_ps(e, "device_duration_ps",
                                         e.duration_ns)))
        out[plane.name] = sorted(ops, key=lambda o: (o.start_ps, -o.dur_ps))
    return out


def host_spans(planes: list) -> list[tuple[str, int, int]]:
    """(name, start_ps, end_ps) of the host threads' events."""
    spans = []
    for plane in planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                s = int(round(e.start_ns * 1000))
                spans.append((e.name, s, s + int(round(e.duration_ns
                                                        * 1000))))
    return spans


def leaves(ops: list[Op]) -> list[Op]:
    """Ops that enclose no other op (``ops`` sorted by start, longest
    first): a ``while`` spanning its body's ops is left out."""
    container = [False] * len(ops)
    stack: list[int] = []
    for i, o in enumerate(ops):
        while stack and ops[stack[-1]].end_ps <= o.start_ps:
            stack.pop()
        if stack and o.end_ps <= ops[stack[-1]].end_ps:
            container[stack[-1]] = True
        stack.append(i)
    return [o for o, c in zip(ops, container) if not c]


def union_ps(ops: list[Op]) -> int:
    total, cur_s, cur_e = 0, None, None
    for o in sorted(ops, key=lambda o: o.start_ps):
        if cur_e is None or o.start_ps > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = o.start_ps, o.end_ps
        else:
            cur_e = max(cur_e, o.end_ps)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def hlo_classes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``"scatter"`` or ``"gather"`` for the fusions
    (and plain ops) of an HLO module text that scatter or gather."""
    comps: dict[str, tuple[set, set]] = {}
    cur = None
    instr_of: dict[str, tuple[str, str]] = {}
    name_re = re.compile(r"^\s*(?:ROOT )?%?(?P<n>[\w.\-]+) = ")
    for line in hlo_text.splitlines():
        m = _COMP.match(line)
        if m and " = " not in line:
            cur = m.group("comp")
            comps[cur] = (set(), set())
            continue
        m = _INSTR.match(line)
        if m and cur is not None:
            op, rest = m.group("op"), m.group("rest")
            comps[cur][0].add(op)
            c = _CALLS.search(rest)
            if c:
                comps[cur][1].add(c.group("comp"))
            n = name_re.match(line).group("n")
            instr_of[n] = (op, c.group("comp") if c else "")

    memo: dict[str, set] = {}

    def opcodes(comp: str, seen=()) -> set:
        if comp in memo:
            return memo[comp]
        if comp not in comps or comp in seen:
            return set()
        ops, called = comps[comp]
        acc = set(ops)
        for c in called:
            acc |= opcodes(c, seen + (comp,))
        memo[comp] = acc
        return acc

    out = {}
    for n, (op, comp) in instr_of.items():
        inner = {op} | (opcodes(comp) if op == "fusion" else set())
        if "scatter" in inner:
            out[n] = "scatter"
        elif "gather" in inner or "table_gather" in n:
            out[n] = "gather"
    return out


def op_class(op: Op, classes: dict[str, str]) -> str:
    base = op.opcode.removesuffix("-start").removesuffix("-done")
    if base in COLLECTIVES:
        return "collective"
    if op.name in classes:
        return classes[op.name]
    if "table_gather" in op.name or op.opcode == "gather":
        return "gather"
    if op.opcode == "scatter":
        return "scatter"
    return "other"


@dataclasses.dataclass
class TraceSummary:
    """Per-device leaf ops of a traced window."""

    per_device: dict[str, list[Op]]
    classes: dict[str, str]
    host: list

    @property
    def n_devices(self) -> int:
        return len(self.per_device)

    def busy_s(self) -> float:
        """Union of leaf-op intervals, in seconds, averaged over devices."""
        if not self.per_device:
            return 0.0
        return sum(union_ps(ops) for ops in self.per_device.values()) / (
            1e12 * len(self.per_device))

    def class_s(self, cls: str) -> float:
        """Device time of the leaf ops of one class, averaged over
        devices, in seconds."""
        if not self.per_device:
            return 0.0
        return sum(o.dur_ps for ops in self.per_device.values() for o in ops
                   if op_class(o, self.classes) == cls) / (
            1e12 * len(self.per_device))

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` ops (by instruction name and class) that took most
        device time, averaged over devices, in seconds."""
        acc: dict[str, float] = {}
        for ops in self.per_device.values():
            for o in ops:
                key = f"{o.name} [{op_class(o, self.classes)}]"
                acc[key] = acc.get(key, 0.0) + o.dur_ps / 1e12
        n = max(1, len(self.per_device))
        return sorted(([k, v / n] for k, v in acc.items()),
                      key=lambda kv: -kv[1])[:k]

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest gaps between leaf ops on the first device,
        each named by the innermost host event that covers its middle."""
        if not self.per_device:
            return []
        ops = sorted(next(iter(self.per_device.values())),
                     key=lambda o: o.start_ps)
        gaps, end = [], None
        for o in ops:
            if end is not None and o.start_ps > end:
                gaps.append((end, o.start_ps))
            end = o.end_ps if end is None else max(end, o.end_ps)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:k]:
            mid = (s + e) // 2
            cover = [h for h in self.host if h[1] <= mid <= h[2]]
            name = (min(cover, key=lambda h: h[2] - h[1])[0] if cover
                    else "no host event")
            out.append([name, (e - s) / 1e12])
        return out


def load(trace_dir: str, hlo_text: str) -> TraceSummary:
    planes = read_planes(find_xplane(trace_dir))
    per_device = {k: leaves(v) for k, v in device_ops(planes).items()}
    return TraceSummary(per_device=per_device,
                        classes=hlo_classes(hlo_text),
                        host=host_spans(planes))
