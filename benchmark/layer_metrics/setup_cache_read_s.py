"""Seconds spent loading programs from the persistent compile cache
before the window's opening (``compile.cache_read_s``): the backend
duration of every hit, key computation, read and deserialisation."""

from benchmark.startup_records import compile_value


def read(ctx):
    return compile_value(ctx, "cache_read_s")
