"""The comparison that decides ``correct``.

The program's run is replayed by the plain reference
(:mod:`bench.reference`) from the same initial state, drive and
connectivity, teacher-forced by the raster the program served.  The
numbers compared:

- ``edges_differ``: synapses of the program's built network that are not
  the configuration's (pre, post, delay, channel, weight), after both
  sides are put in one order; the limit is 0.
- ``spike_gap_mV``: the widest gap by which a served spike decision
  contradicts the reference's membrane potential at that step: how far
  below threshold a neuron sat that was served as firing (the whole
  threshold-to-reset distance where it was refractory), or how far above
  threshold one sat that was served as silent.
- ``v_gap_rel``: the widest difference of a membrane potential at the end
  of the run, over the widest deflection from rest that the reference
  holds then.
- ``syn_gap_rel``: the widest difference of a synaptic current at the end,
  over the largest current of its channel in the reference; the wider of
  the two channels.
- ``w_gap_pA``: the widest difference of a plastic weight at the end
  (only where plasticity is on).

The end state is compared relative to its scale because float32 sums
round in proportion to their size: a burst that drives currents to
1e5-1e6 pA leaves gaps of tens of pA that are rounding, while a quiet
run's currents are ~1e3 pA.

Each has its limit in the cell's file under ``bench/workloads/``; a run
is correct when no number exceeds its limit.
"""

from __future__ import annotations

import numpy as np

__all__ = ["match_edges", "spike_gaps", "readings", "judge"]


def _order(e: dict, n: int, d_max: int) -> np.ndarray:
    key = (e["post"] * n + e["pre"]) * (d_max + 1) + e["delay"]
    return np.lexsort((e["w"], e["channel"], key))


def match_edges(prog: dict, ref: dict, n: int, d_max: int):
    """Put the program's and the reference's synapses in one order.
    Returns (number of synapses that differ, for each reference synapse
    the index of its weight in the program's weight vector)."""
    if prog["pre"].size != ref["pre"].size:
        return max(prog["pre"].size, ref["pre"].size), None
    po, ro = _order(prog, n, d_max), _order(ref, n, d_max)
    same = np.ones(po.size, bool)
    for k in ("pre", "post", "delay", "channel", "w"):
        same &= prog[k][po] == ref[k][ro]
    index = np.empty(po.size, np.int64)
    index[ro] = prog["index"][po]
    return int((~same).sum()), index


def spike_gaps(served: np.ndarray, rep, v_th: np.ndarray,
               v_reset: np.ndarray) -> np.ndarray:
    """Per step, the widest gap between a served spike decision and the
    reference's potential (0 where every decision agrees)."""
    v = rep.v_pre.astype(np.float64)
    refr = rep.refractory
    gap = np.where(served & refr, v_th - v_reset, 0.0)
    gap = np.maximum(gap, np.where(served & ~refr, v_th - v, 0.0))
    gap = np.maximum(gap, np.where(~served & ~refr, v - v_th, 0.0))
    return gap.max(axis=1)


def _gap(a, b) -> float:
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


def _rel_gap(a, b, rest=0.0) -> float:
    """``max|a - b|`` over ``max|b - rest|`` (the gap itself where the
    reference is all at rest)."""
    scale = float(np.max(np.abs(np.asarray(b, np.float64) - rest)))
    gap = _gap(a, b)
    return gap / scale if scale > 0.0 else gap


def readings(ref, cand: dict, consts: dict,
             plastic: np.ndarray | None) -> dict:
    """The numbers of one candidate (the program's run, or a control) held
    against the float32 reference ``ref``.  ``cand`` has ``served``
    (steps, N), the end state ``v``, ``syn_ex``, ``syn_in`` and, for a
    plastic run, ``w`` in reference edge order; ``consts`` are the
    reference's per-neuron constants; ``plastic`` masks the plastic
    synapses."""
    gaps = spike_gaps(cand["served"], ref, consts["v_th"], consts["v_reset"])
    out = {"spike_gap_mV": float(gaps.max()),
           "v_gap_rel": _rel_gap(cand["v"], ref.v, consts["e_l"]),
           "syn_gap_rel": max(_rel_gap(cand["syn_ex"], ref.syn_ex),
                              _rel_gap(cand["syn_in"], ref.syn_in))}
    if plastic is not None:
        out["w_gap_pA"] = _gap(cand["w"][plastic], ref.w[plastic])
    return out, gaps


def judge(numbers: dict, limits: dict):
    """``correct`` and the compared numbers, each beside its limit.  A
    number without a limit, or a limit without a number, is not correct."""
    compared = {}
    ok = set(numbers) == set(limits)
    for k in sorted(set(numbers) | set(limits)):
        v, lim = numbers.get(k), limits.get(k)
        compared[k] = {"value": v, "limit": lim}
        ok = ok and v is not None and lim is not None and v <= lim
    return ok, compared
