"""Device milliseconds per simulated step of the ops the program runs in
its ``sweep`` phase: the synaptic sweep (the ring lookup and the two
``segment_sum``s) and the ring write."""

from pathlib import Path

from bench import phases


def read(ctx):
    v = phases.ms_per_step(ctx, "sweep",
                           Path(__file__).resolve().parents[2])
    return v if v else None
