"""The grouped expert products inside the decode chunk of a server that
holds a *share* of the experts, as a share of their roofline, in percent.
The work required is taken from the *counted* routing of the traced seconds
(the program's counters between the two ``/healthz`` reads around the
trace, per expert layer and step, times the layer-steps of the chunk
executions the trace holds): the assignments that landed *here* x three
products of hidden x moe_intermediate, and the bytes of the weights of the
held experts *touched*, once each, plus the rows
(``benchmark/flops_qwen3_next.py``). Over the device time of the events
matching ``trace_names.moe_gmm`` inside those executions (a prefill's
grouped products are another shape and another program)."""

from benchmark import decode_chunks, flops, flops_qwen3_next


def read(ctx):
    found = decode_chunks.executions(ctx)
    steps = decode_chunks.steps_per_chunk(ctx)
    pattern = (ctx.config.get("trace_names") or {}).get("moe_gmm")
    layer_steps = decode_chunks.traced_counter(ctx, "moe.layer_steps_n")
    here = decode_chunks.traced_counter(ctx, "moe.assignments_here_n")
    m = (ctx.config.get("model") or {}).get("config")
    if (not found or not steps or not pattern or not layer_steps or here is None or ctx.peaks is None
            or m is None or "linear_num_value_heads" not in m):
        return None
    kernel_s = decode_chunks.op_seconds_inside(ctx, found, pattern)
    if kernel_s <= 0:
        return None
    traced_layer_steps = len(found) * steps * flops_qwen3_next.expert_layers(m)
    assignments = here / layer_steps * traced_layer_steps
    touched = decode_chunks.traced_counter(ctx, "moe.experts_touched_n") / layer_steps * traced_layer_steps
    least_s, _ = flops.roofline_seconds(
        flops_qwen3_next.moe_gmm_flops(m, assignments),
        flops_qwen3_next.moe_gmm_bytes(m, touched, assignments), ctx.peaks)
    return 100.0 * least_s / kernel_s
