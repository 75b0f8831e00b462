"""The chunked delta rule's sequential loop in the prefill programs against
its roofline, in percent: for every event of the traced seconds whose name
matches the configuration's ``trace_names.gdn_scan_loop`` (the ``while``
that carries a layer's matrix state from chunk to chunk under the scope
``gdn.chunk``: one a delta layer a prefill), the least time the chip could
take for what the loop carries over the chunks it ran (three products with
the state, the one that waits for them and the state's decay a chunk; the
streamed operands at the dtypes they are carried in, four in bf16, two in
float32; the chunks read from the event's own stacked operand, ``[chunks,
rows, heads, 1, 64, ...]``; the greater of operations over the bf16 peak
and bytes over the HBM rate: ``benchmark/flops_olmo_hybrid.py``), summed,
over the events' device seconds. What the chunked form computes for all
chunks at once (the triangular inverse among it) runs before the loop as
operations no name tells apart, and is in neither term."""

import re

from benchmark import flops, flops_olmo_hybrid, trace_events


def read(ctx):
    m = (ctx.config.get("model") or {}).get("config")
    found = trace_events.matching(ctx, "gdn_scan_loop")
    if not found or m is None or ctx.peaks is None or "linear_allow_neg_eigval" not in m:
        return None
    one_layer = dict(m, num_hidden_layers=1, layer_types=["linear_attention"])
    stacked = re.compile(r"f32\[(\d+),\d+,%d,1,%d,%d\]" % (
        m["linear_num_key_heads"], flops_olmo_hybrid.DELTA_CHUNK, m["linear_value_head_dim"]))
    least = took = 0.0
    for seconds, text in found:
        chunks = stacked.findall(text)
        if not chunks:
            return None
        n = int(chunks[0]) * flops_olmo_hybrid.DELTA_CHUNK
        least += flops.roofline_seconds(flops_olmo_hybrid.scan_carry_flops(one_layer, n),
                                        flops_olmo_hybrid.scan_carry_bytes(one_layer, n), ctx.peaks)[0]
        took += seconds
    return 100.0 * least / took if took > 0 else None
