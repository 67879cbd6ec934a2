"""A configuration file's network table as the program's ``NetworkSpec``.

The table (``network`` in the file) is the one statement of the
deployment: areas, populations, projections, neuron groups, the longest
delay and the connectivity rule, under the field names of the program's
spec types.  The STDP parameters are the file's ``stdp``.  Nothing here
calls the program's scenario factories; the reference
(:mod:`bench.reference`) reads the same table.
"""

from __future__ import annotations

__all__ = ["load"]


def load(data: dict, seed: int, plastic: bool):
    """``(NetworkSpec, STDPParams | None)`` of the configuration ``data``
    with synapses drawn from ``seed``; plastic projections stay plastic
    only when ``plastic``."""
    from repro.core import builder, snn, stdp

    t = data["network"]
    spec = builder.NetworkSpec(
        areas=[builder.AreaSpec(**a) for a in t["areas"]],
        groups=[snn.LIFParams(**g) for g in t["groups"]],
        populations=[builder.Population(**p) for p in t["populations"]],
        projections=[builder.Projection(**dict(
            p, plastic=bool(p["plastic"] and plastic)))
            for p in t["projections"]],
        max_delay=int(t["max_delay"]), seed=int(seed),
        connectivity=t["connectivity"])
    return spec, (stdp.STDPParams(**data["stdp"]) if plastic else None)
