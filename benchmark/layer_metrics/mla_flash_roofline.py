"""The latent-attention flash kernels' share of their roofline, in
percent, at q/k ``qk_nope + qk_rope`` wide and v ``v_head_dim`` wide: the
least time the chip could take for the attention one step needs (forward
and backward of every block, the MTP module's included;
``benchmark/flops_mla_moe.py``) over the device time per step of the
kernel events matching the configuration's
``trace_names.mla_flash_kernel``. Whole-block remat runs the forward
kernel twice: time spent, not work required, so it lowers the share."""

import re

from benchmark import flops, flops_mla_moe


def read(ctx):
    if ctx.trace is None or "cycles" not in ctx.stamps or ctx.peaks is None:
        return None
    pattern = ctx.config.get("trace_names", {}).get("mla_flash_kernel")
    if not pattern or not ctx.trace.used_planes():
        return None
    _, steps = ctx.trace.main_module()
    kernel_s = sum(v[0] for n, v in ctx.trace.op_seconds().items() if re.search(pattern, n))
    if not steps or kernel_s <= 0:
        return None
    m, t = ctx.config["model"]["config"], ctx.traffic["params"]
    rows = t["batch"] // ctx.run.chips  # one chip's share of the batch
    layers = flops_mla_moe.attention_layers(m)
    least_s, _ = flops.roofline_seconds(
        layers * flops_mla_moe.mla_flash_flops(m, rows, t["seq"]),
        layers * flops_mla_moe.mla_flash_bytes(m, rows, t["seq"]), ctx.peaks)
    return 100.0 * least_s * len(steps) / kernel_s
