"""The grouped expert products inside the block chunk, as a share of their
roofline, in percent. The work required is taken from the *counted* routing
of the traced seconds (the program's counters between the two ``/healthz``
reads around the trace, per expert layer and pass, times the layer-passes of
the chunk executions the trace holds): assignments x three products of
hidden x moe_intermediate against the chip's peak, and the bytes of the
weights of the experts *touched*, once each, plus the rows, against its HBM
rate: the larger of the two (``benchmark/flops_sdar_moe.py``). Over the
device time of the events matching ``trace_names.moe_gmm`` inside those
executions (a prefill's grouped products are another program)."""

from benchmark import decode_chunks, flops, flops_sdar_moe


def read(ctx):
    found = decode_chunks.executions(ctx)
    passes = decode_chunks.steps_per_chunk(ctx)
    m = (ctx.config.get("model") or {}).get("config")
    pattern = (ctx.config.get("trace_names") or {}).get("moe_gmm")
    layer_steps = decode_chunks.traced_counter(ctx, "moe.layer_steps_n")
    if (not found or not passes or not pattern or not layer_steps or ctx.peaks is None
            or m is None or "block_length" not in m):
        return None
    kernel_s = decode_chunks.op_seconds_inside(ctx, found, pattern)
    if kernel_s <= 0:
        return None
    traced_layer_steps = len(found) * passes * flops_sdar_moe.layers(m)
    assignments = decode_chunks.traced_counter(ctx, "moe.assignments_n") / layer_steps * traced_layer_steps
    touched = decode_chunks.traced_counter(ctx, "moe.experts_touched_n") / layer_steps * traced_layer_steps
    least_s, _ = flops.roofline_seconds(
        flops_sdar_moe.moe_gmm_flops(m, assignments),
        flops_sdar_moe.moe_gmm_bytes(m, touched, assignments), ctx.peaks)
    return 100.0 * least_s / kernel_s
