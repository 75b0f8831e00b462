"""Seconds a step of the train loop spends outside the step function and
the save: the program's ``train.data_wait`` (``next`` on the input
iterator) plus ``train.report`` (the agent's progress report, the caller's
``on_step``, the log branch) of the same step. Median over the traced
window's steps; a cycle's last step, whose ``on_step`` may sync, is one in
ten and leaves the median alone."""

from benchmark.program_spans import step_median


def read(ctx):
    return step_median(ctx, lambda step: step["data_wait_s"] + step["report_s"])
