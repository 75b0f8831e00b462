"""The flash attention kernels' share of their roofline, in percent: the
least time the chip could take for the attention one step needs (forward
and backward of every layer, from ``benchmark/flops.py``: the larger of
operations over the bf16 peak and bytes over the HBM peak) over the device
time the kernels took per step in the trace. Kernel events are those whose
name matches the configuration's ``trace_names.flash_kernel``. The remat
of ``models/gpt.py`` runs the forward kernel twice; the second run is time
spent and not work required, so it lowers the share."""

import re

from benchmark import flops


def read(ctx):
    if ctx.trace is None or "cycles" not in ctx.stamps or ctx.peaks is None:
        return None
    pattern = ctx.config.get("trace_names", {}).get("flash_kernel")
    if not pattern or not ctx.trace.used_planes():
        return None
    _, steps = ctx.trace.main_module()
    kernel_s = sum(v[0] for n, v in ctx.trace.op_seconds().items() if re.search(pattern, n))
    if not steps or kernel_s <= 0:
        return None
    g, t = ctx.config["gpt_config"], ctx.traffic["params"]
    # one chip's share of the batch: the kernel runs per shard
    rows = t["batch"] // ctx.run.chips
    need_flops = g["num_layers"] * flops.flash_attention_flops(
        rows, g["num_heads"], t["seq"], g["head_dim"])
    need_bytes = g["num_layers"] * flops.flash_attention_bytes(
        rows, g["num_heads"], t["seq"], g["head_dim"])
    least_s, _ = flops.roofline_seconds(need_flops, need_bytes, ctx.peaks)
    return 100.0 * least_s * len(steps) / kernel_s
