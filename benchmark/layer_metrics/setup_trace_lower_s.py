"""Seconds of Python tracing and MLIR lowering of every program built
before the window's opening (``compile.trace_s`` + ``compile.lower_s``).
Only programs that reached the backend count: a function that was lowered
and never compiled (a traced training run reads the step's lowered text
for its own check) is not in it. Neither the cache nor a faster compiler
shortens these seconds; fewer or smaller programs do."""

from benchmark.startup_records import compile_value


def read(ctx):
    return compile_value(ctx, "trace_s", "lower_s")
