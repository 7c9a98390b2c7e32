"""Compile seconds (lowering + backend compile, persistent-cache loads
included) from process start to the window's opening, by
``benchmark/meter.CompileMeter``."""


def read(ctx):
    return ctx.get("compile_s_setup")
