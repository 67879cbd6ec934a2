"""Device milliseconds per simulated step of the ops that gather (HLO
gathers, fusions that hold one, TPU table-gather calls): the sweep's ring
lookup and STDP's trace lookups."""


def read(ctx):
    if ctx.trace is None or ctx.steps <= 0:
        return None
    s = ctx.trace.class_s("gather")
    return 1e3 * s / ctx.steps if s > 0 else None
