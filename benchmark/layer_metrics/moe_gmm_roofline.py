"""The grouped expert products' share of their roofline, in percent. The
work required is taken from the *counted* assignments of the steps the
trace holds (the worker's ``counters_traced``: the load drifts inside a
run, so the window's mean would set one load's work against another's
time): token-assignments that landed on the experts held, per step, times
three products of hidden x moe_intermediate, forward once and backward
twice; the bytes are the held experts' weights read once a pass
(``benchmark/flops_mla_moe.py``). Over the device time per step of the
events matching the configuration's ``trace_names.moe_gmm``. The remat's
second forward is time spent, not work required."""

import re

from benchmark import flops, flops_mla_moe


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    counters = ctx.stamps.get("counters_traced") or {}
    pattern = ctx.config.get("trace_names", {}).get("moe_gmm")
    if not pattern or not counters.get("train.steps_counted") or not ctx.trace.used_planes():
        return None
    _, steps = ctx.trace.main_module()
    kernel_s = sum(v[0] for n, v in ctx.trace.op_seconds().items() if re.search(pattern, n))
    if not steps or kernel_s <= 0:
        return None
    m = ctx.config["model"]["config"]
    per_step = counters["moe.assignments_here"] / counters["train.steps_counted"]
    least_s, _ = flops.roofline_seconds(
        flops_mla_moe.moe_gmm_flops(m, per_step),
        flops_mla_moe.moe_gmm_bytes(m, flops_mla_moe.expert_layers(m)), ctx.peaks)
    return 100.0 * least_s * len(steps) / kernel_s
