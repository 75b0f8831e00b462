"""Device time of one train step, first operation to last: the median
over the traced window of the executions of the program that took most
device time (the step), from the trace's ``XLA Modules`` line."""

import statistics


def read(ctx):
    if ctx.trace is None or "cycles" not in ctx.stamps:
        return None
    _, durations = ctx.trace.main_module()
    return statistics.median(durations) if durations else None
