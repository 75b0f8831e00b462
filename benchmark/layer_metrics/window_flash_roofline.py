"""The window layers' flash kernels' share of their roofline, in percent:
the least time the chip could take for the attention the
``sliding_attention`` layers of one step need over the pairs the window
leaves (forward and backward; ``benchmark/flops_mellum.py``), over the
device time per step of the kernel events under the scope
``swa.attend_window`` (the configuration's
``trace_names.window_flash_kernel``). The band's kernels compute 1.25 times
what the mask leaves (whole 256-wide sub-squares at both ends of the band):
time spent, not work required."""

import re

from benchmark import flops, flops_mellum

KIND, PATTERN = "sliding_attention", "window_flash_kernel"


def kernel_seconds(ctx, pattern_name: str):
    """(device seconds of the events matching the configuration's
    ``trace_names[pattern_name]``, the step's executions) or None."""
    if ctx.trace is None or "cycles" not in ctx.stamps:
        return None
    pattern = ctx.config.get("trace_names", {}).get(pattern_name)
    if not pattern or not ctx.trace.used_planes():
        return None
    _, steps = ctx.trace.main_module()
    kernel_s = sum(v[0] for n, v in ctx.trace.op_seconds().items() if re.search(pattern, n))
    return (kernel_s, steps) if steps and kernel_s > 0 else None


def flash_roofline(ctx, kind: str, pattern_name: str):
    found = kernel_seconds(ctx, pattern_name)
    if found is None or ctx.peaks is None:
        return None
    kernel_s, steps = found
    m, t = ctx.config["model"]["config"], ctx.traffic["params"]
    rows = t["batch"] // ctx.run.chips  # one chip's share of the batch
    layers = flops_mellum.layers_of(m, kind)
    least_s, _ = flops.roofline_seconds(
        layers * flops_mellum.flash_flops(m, kind, rows, t["seq"]),
        layers * flops_mellum.flash_bytes(m, rows, t["seq"]), ctx.peaks)
    return 100.0 * least_s * len(steps) / kernel_s


def read(ctx):
    return flash_roofline(ctx, KIND, PATTERN)
