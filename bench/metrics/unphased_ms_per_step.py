"""Device milliseconds per simulated step of the ops in none of the
program's phases: scan bookkeeping, carry copies, the raster output and
``engine.run``'s weight conversions.  0.0 where every op has a phase; it
jumps where a change leaves part of the step out of the phases."""

from pathlib import Path

from bench import phases


def read(ctx):
    return phases.ms_per_step(ctx, None, Path(__file__).resolve().parents[2])
