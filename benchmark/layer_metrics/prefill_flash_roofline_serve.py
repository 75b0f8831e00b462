"""The tiled prefill's flash kernel against its roofline, in percent: for
every kernel event of the traced seconds whose name matches the
configuration's ``trace_names.prefill_flash_kernel`` (the forward kernel
under the scope ``olmo.attend_prefill``: one call a full-attention layer a
prefill), the least time the chip could take for the causal half of the
call it was given (``Q K^T`` and ``P V`` over the width read from the
event's own operand, ``[heads, width, head size]``; the greater of
operations over the bf16 peak and bytes over the HBM rate:
``benchmark/flops_olmo_hybrid.py``), summed, over the events' device
seconds. A bucket's padding is inside the call's width: what it costs is
``serve_prefill_pad_share``'s to say."""

import re

from benchmark import flops, flops_olmo_hybrid, trace_events

WIDTH = re.compile(r"bf16\[(\d+),(\d+),(\d+)\]")  # the kernel's first operand: [heads, width, head size]


def read(ctx):
    m = (ctx.config.get("model") or {}).get("config")
    found = trace_events.matching(ctx, "prefill_flash_kernel")
    if not found or m is None or ctx.peaks is None or "linear_allow_neg_eigval" not in m:
        return None
    one_layer = dict(m, num_hidden_layers=1, layer_types=["full_attention"])
    least = took = 0.0
    for seconds, text in found:
        shapes = [tuple(int(v) for v in s) for s in WIDTH.findall(text)]
        widths = [w for h, w, d in shapes if h == m["num_attention_heads"] and d == flops_olmo_hybrid.head_dim(m)]
        if not widths:
            return None
        least += flops.roofline_seconds(flops_olmo_hybrid.flash_causal_flops(one_layer, widths[0]),
                                        flops_olmo_hybrid.flash_bytes(one_layer, widths[0]), ctx.peaks)[0]
        took += seconds
    return 100.0 * least / took if took > 0 else None
