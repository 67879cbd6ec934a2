"""Host seconds of the program's builder: spec to placed-ready graph
(``builder.build_shards`` or ``distributed.prepare_stacked``)."""


def read(ctx):
    return ctx.times.get("build_s")
