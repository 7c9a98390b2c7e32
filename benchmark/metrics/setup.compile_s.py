"""Compile seconds (lowering + backend compile, persistent-cache loads
included) from process start to the window's opening, by
``benchmark/meter.CompileMeter``.

The entry ``setup.compile_s`` lists the cells it reads; a later cell
gets a split entry of its own, ``setup.compile_s.<split>`` listing it,
which this reader serves by its stem (``run.metric_reader``)."""


def read(ctx):
    return ctx.get("compile_s_setup")
