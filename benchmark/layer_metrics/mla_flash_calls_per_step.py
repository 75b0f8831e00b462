"""How many latent-attention flash kernels one training step runs: the
device events of the traced steps that match the configuration's
``trace_names.mla_flash_kernel``, over the number of steps. A block needs
three (forward, dk/dv, dq); a block whose backward pass runs the forward
kernel again, to have the ``out`` and ``lse`` it did not keep, shows
four. The count beside ``mla_flash_roofline``'s time: that share moves
with either."""

import re


def read(ctx):
    if ctx.trace is None or "cycles" not in ctx.stamps:
        return None
    pattern = ctx.config.get("trace_names", {}).get("mla_flash_kernel")
    if not pattern or not ctx.trace.used_planes():
        return None
    _, steps = ctx.trace.main_module()
    calls = sum(v[1] for n, v in ctx.trace.op_seconds().items() if re.search(pattern, n))
    if not steps or not calls:
        return None
    return calls / len(steps)
