"""The whole pass of the block chunk against its roofline, in percent,
whatever implements it: the bytes one pass has to move (the attention and
router matrices and the head once; the experts at least one row chose, by the
program's ``moe.experts_touched`` a layer-step over the traced seconds; the
rows' keys and values **at their real lengths**, by ``kv_positions_valid``
over the traced seconds' passes: ``benchmark/flops_sdar_moe.py``) over the
chip's HBM rate, over the device seconds of a pass (the chunk program's
median execution over its passes, what ``serve_decode_step_device_s``
reads). A pass of 64 positions is bound by the bytes it moves."""

import statistics

from benchmark import decode_chunks, flops_sdar_moe


def read(ctx):
    found = decode_chunks.executions(ctx)
    passes = decode_chunks.steps_per_chunk(ctx)
    m = (ctx.config.get("model") or {}).get("config")
    slots = (ctx.stamps.get("healthz") or {}).get("slots")
    valid = decode_chunks.traced_counter(ctx, "kv_positions_valid_n")
    row_steps = decode_chunks.traced_counter(ctx, "row_steps_n")
    touched = decode_chunks.traced_counter(ctx, "moe.experts_touched_n")
    layer_steps = decode_chunks.traced_counter(ctx, "moe.layer_steps_n")
    if (not found or not passes or not slots or m is None or ctx.peaks is None or "block_length" not in m
            or valid is None or not row_steps or touched is None or not layer_steps):
        return None
    valid_a_pass = valid / (row_steps / slots)  # the live rows' real lengths, summed, at a mean pass
    touched_a_pass = touched / layer_steps * flops_sdar_moe.layers(m)
    pass_s = statistics.median(e - s for s, e in found) / 1e9 / passes
    return 100.0 * flops_sdar_moe.pass_bytes(m, touched_a_pass, valid_a_pass) / ctx.peaks["hbm_bytes_per_s"] / pass_s
