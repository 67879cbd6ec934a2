"""Device milliseconds per simulated step of the ops the program runs in
its ``stdp`` phase: the STDP weight update with its trace lookups, the
pre-trace ``segment_max`` and the trace update."""

from pathlib import Path

from bench import phases


def read(ctx):
    v = phases.ms_per_step(ctx, "stdp",
                           Path(__file__).resolve().parents[2])
    return v if v else None
