"""The window layers' flash kernels' share of the step's device time, in
percent: the device time of the kernel events under ``swa.attend_window``
(``trace_names.window_flash_kernel``) over the summed device time of the
step's executions in the trace. A program that masked the window but
visited every tile would read ~3.5 times this."""

from benchmark.layer_metrics.window_flash_roofline import PATTERN, kernel_seconds


def read(ctx):
    found = kernel_seconds(ctx, PATTERN)
    if found is None:
        return None
    kernel_s, steps = found
    return 100.0 * kernel_s / sum(steps)
