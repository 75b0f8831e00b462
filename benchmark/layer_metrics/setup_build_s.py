"""Seconds of model build, state or parameters on the device and engine
construction. Training: ``startup.init_state`` (``init_train_state``: the
shardings and the jitted init program, its compile with it) and
``startup.build_step``. Serving: ``startup.build_model``,
``startup.params`` (``serve.params_init`` nests in it) and
``startup.engine`` (the cache's allocation). A cross-cut, not a partition:
the init program's trace, lowering and compile (or cache read) happen
inside these phases and are counted again by the compile metrics
(``setup_trace_lower_s``, ``setup_backend_compile_s``,
``setup_cache_read_s``)."""

from benchmark.startup_records import phase_seconds


def read(ctx):
    return phase_seconds(ctx, "init_state", "build_step", "build_model", "params", "engine")
