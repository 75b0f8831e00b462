"""Programs the chip-holding process built or loaded before the window's
opening (``compile.programs``): every one costs tracing and lowering even
when the cache has it."""

from benchmark.startup_records import compile_value


def read(ctx):
    return compile_value(ctx, "programs")
