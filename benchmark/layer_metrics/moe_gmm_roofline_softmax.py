"""The grouped expert products' share of their roofline under the softmax
router of the ``mellum`` family, in percent: ``moe_gmm_roofline``'s
arithmetic over this configuration's shapes. The work required is taken
from the *counted* assignments of the steps the trace holds (the worker's
``counters_traced``): token-assignments that landed on the experts held,
per step, times three products of hidden x moe_intermediate, forward once
and backward twice; the bytes are the held experts' weights read once a
pass (``benchmark/flops_mellum.py``). Over the device time per step of the
events matching the configuration's ``trace_names.moe_gmm``."""

from benchmark import flops, flops_mellum
from benchmark.layer_metrics.window_flash_roofline import kernel_seconds


def read(ctx):
    counters = ctx.stamps.get("counters_traced") or {}
    found = kernel_seconds(ctx, "moe_gmm")
    if found is None or ctx.peaks is None or not counters.get("train.steps_counted"):
        return None
    kernel_s, steps = found
    m = ctx.config["model"]["config"]
    per_step = counters["moe.assignments_here"] / counters["train.steps_counted"]
    least_s, _ = flops.roofline_seconds(
        flops_mellum.moe_gmm_flops(m, per_step), flops_mellum.moe_gmm_bytes(m), ctx.peaks)
    return 100.0 * least_s * len(steps) / kernel_s
