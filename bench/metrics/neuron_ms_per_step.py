"""Device milliseconds per simulated step of the ops the program runs in
its ``neuron`` phase: the external drive and the neuron update."""

from pathlib import Path

from bench import phases


def read(ctx):
    v = phases.ms_per_step(ctx, "neuron",
                           Path(__file__).resolve().parents[2])
    return v if v else None
