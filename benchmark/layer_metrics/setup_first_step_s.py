"""Seconds of the training worker's first step, call to result ready
(``startup.first_step``): the step program's tracing, lowering and compile
or cache read, then its first execution."""

from benchmark.startup_records import phase_seconds


def read(ctx):
    return phase_seconds(ctx, "first_step")
