"""Programs compiled before the window's opening although the cache was
asked for them (``compile.cache_misses``): in a warm run only those under
the cache's minimum compile time, which are never stored; more says the
traced run was a cold one (per-layer metrics come from the traced run,
which may be a call's first) or that the cache lost programs."""

from benchmark.startup_records import compile_value


def read(ctx):
    return compile_value(ctx, "cache_misses")
