"""Seconds the train loop spends inside one call of its step function
(the program's ``train.step_dispatch`` span around ``self.step_fn``): the
host's cost of enqueueing a step, or, once the device's queue is full,
the wait for room in it. Median over the traced window's steps."""

from benchmark.program_spans import step_median


def read(ctx):
    return step_median(ctx, lambda step: step["dispatch_s"])
