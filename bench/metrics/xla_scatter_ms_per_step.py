"""Device milliseconds per simulated step of the ops that scatter (HLO
scatters and fusions that hold one): the sweep's ``segment_sum`` and
STDP's pre-trace ``segment_max``."""


def read(ctx):
    if ctx.trace is None or ctx.steps <= 0:
        return None
    s = ctx.trace.class_s("scatter")
    return 1e3 * s / ctx.steps if s > 0 else None
