"""Host seconds of lowering and compiling the window's call (a load from
the persistent compilation cache when it hits)."""


def read(ctx):
    return ctx.times.get("compile_s")
