"""The grouped expert products inside the decode chunk, as a share of their
roofline, in percent. The work required is taken from the *counted*
routing of the traced seconds (the program's counters between the two
``/healthz`` reads around the trace, per expert layer and step, times the
layer-steps of the chunk executions the trace holds): assignments x three
products of hidden x moe_intermediate, and the bytes of the weights of the
experts *touched*, once each, plus the rows (``benchmark/flops_lfm2_moe.py``).
Over the device time of the events matching ``trace_names.moe_gmm`` inside
those executions (a prefill's grouped products are another shape and
another program)."""

from benchmark import decode_chunks, flops, flops_lfm2_moe


def read(ctx):
    found = decode_chunks.executions(ctx)
    steps = decode_chunks.steps_per_chunk(ctx)
    pattern = (ctx.config.get("trace_names") or {}).get("moe_gmm")
    layer_steps = decode_chunks.traced_counter(ctx, "moe.layer_steps_n")
    if not found or not steps or not pattern or not layer_steps or ctx.peaks is None:
        return None
    kernel_s = decode_chunks.op_seconds_inside(ctx, found, pattern)
    if kernel_s <= 0:
        return None
    m = ctx.config["model"]["config"]
    traced_layer_steps = len(found) * steps * flops_lfm2_moe.expert_layers(m)
    assignments = decode_chunks.traced_counter(ctx, "moe.assignments_n") / layer_steps * traced_layer_steps
    touched = decode_chunks.traced_counter(ctx, "moe.experts_touched_n") / layer_steps * traced_layer_steps
    least_s, _ = flops.roofline_seconds(
        flops_lfm2_moe.moe_gmm_flops(m, assignments),
        flops_lfm2_moe.moe_gmm_bytes(m, touched, assignments), ctx.peaks)
    return 100.0 * least_s / kernel_s
