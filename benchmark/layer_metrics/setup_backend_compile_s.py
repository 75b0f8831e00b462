"""Seconds inside XLA compiles of programs the persistent cache did not
have (or that run with no cache), before the window's opening
(``compile.backend_s``). About nothing in a warm run; in a cold one, the
compiler's whole bill."""

from benchmark.startup_records import compile_value


def read(ctx):
    return compile_value(ctx, "backend_s")
