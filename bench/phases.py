"""Device time of the program's named phases in a traced run.

The program runs each phase of its step under a ``jax.named_scope`` whose
name is one of ``repro.core.engine.PHASES`` (sweep, neuron, stdp,
exchange).  XLA keeps the scope path in each compiled instruction's
metadata, ``metadata={op_name="jit(_call)/while/body/closed_call/sweep/
..."}``, in the HLO text that ``bench.run`` saves as ``hlo.txt`` beside the
trace.  An instruction belongs to the outermost component of that path
that equals a phase name (``jit(sweep)`` is no match).  A fusion belongs
to the phase of its own instruction's metadata, whatever the instructions
it fuses carry.  A leaf op of the trace (``bench.trace``) takes the phase
of its instruction; an op in no phase is unphased.

The readers ``bench/metrics/<phase>_ms_per_step.py`` and
``unphased_ms_per_step.py`` find the run's ``hlo.txt`` under
``<root>/.bench_trace/<cell>/``: the newest one whose op classes are the
traced run's (``bench.run`` writes it just before it calls the readers).
Where the program names no phase, as before the scopes existed, every
reader returns None.
"""

from __future__ import annotations

import re
from pathlib import Path

from bench import trace as trace_mod

__all__ = ["program_phases", "hlo_phases", "phase_s", "run_hlo",
           "ms_per_step"]

_NAME = re.compile(r"^\s*(?:ROOT )?%?(?P<n>[\w.\-]+) = ")
_OP_NAME = re.compile(r'\bop_name="(?P<path>[^"]*)"')


def program_phases() -> tuple[str, ...]:
    """The phase names the program defines (none before it had them)."""
    from repro.core import engine
    return tuple(getattr(engine, "PHASES", ()))


def hlo_phases(hlo_text: str, phases) -> dict[str, str]:
    """Instruction name -> phase, for the instructions of an HLO module
    text whose ``op_name`` path has a component equal to a phase name (the
    outermost such component).  Instructions in no phase are left out."""
    phases = set(phases)
    out = {}
    for line in hlo_text.splitlines():
        m = _NAME.match(line)
        o = _OP_NAME.search(line) if m else None
        if o is None:
            continue
        for part in o.group("path").split("/"):
            if part in phases:
                out[m.group("n")] = part
                break
    return out


def phase_s(trace: trace_mod.TraceSummary, phase_of: dict[str, str],
            name: str | None) -> float:
    """Device seconds of the leaf ops in phase ``name`` (``None``: in no
    phase), averaged over devices."""
    if not trace.per_device:
        return 0.0
    return sum(o.dur_ps for ops in trace.per_device.values() for o in ops
               if phase_of.get(o.name) == name) / (
        1e12 * len(trace.per_device))


def run_hlo(root: Path, trace: trace_mod.TraceSummary) -> str | None:
    """The HLO text of the traced run: the newest ``hlo.txt`` under
    ``root/.bench_trace/`` whose op classes are the trace's."""
    files = sorted(Path(root).glob(".bench_trace/*/hlo.txt"),
                   key=lambda p: p.stat().st_mtime_ns, reverse=True)
    for f in files:
        text = f.read_text()
        if trace_mod.hlo_classes(text) == trace.classes:
            return text
    return None


def ms_per_step(ctx, name: str | None, root: Path) -> float | None:
    """Device milliseconds per simulated step of phase ``name`` (``None``:
    of the ops in no phase); None where the trace has no device or the
    program names no phase."""
    if ctx.trace is None or ctx.trace.n_devices == 0 or ctx.steps <= 0:
        return None
    text = run_hlo(root, ctx.trace)
    phase_of = hlo_phases(text, program_phases()) if text else {}
    if not phase_of:
        return None
    return 1e3 * phase_s(ctx.trace, phase_of, name) / ctx.steps
