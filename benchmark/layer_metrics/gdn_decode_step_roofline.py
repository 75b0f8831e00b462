"""The whole decode step of the delta-rule / routed-experts server against
its roofline, in percent, whatever implements it: the bytes one step has to
move (the matrices every step reads once; the held experts at least one row
chose, once each, by the chunk's own counters over the traced seconds;
every slot's matrix state and convolution inputs once in and once out; the
dense keys and values once: ``benchmark/flops_qwen3_next.py``) over the
chip's HBM rate, over the device seconds of a step (the decode chunk
program's median execution over its steps, what
``serve_decode_step_device_s`` reads)."""

import statistics

from benchmark import decode_chunks, flops_qwen3_next


def read(ctx):
    found = decode_chunks.executions(ctx)
    steps = decode_chunks.steps_per_chunk(ctx)
    m = (ctx.config.get("model") or {}).get("config")
    slots = (ctx.stamps.get("healthz") or {}).get("slots")
    touched = decode_chunks.traced_counter(ctx, "moe.experts_touched_n")
    layer_steps = decode_chunks.traced_counter(ctx, "moe.layer_steps_n")
    if (not found or not steps or not slots or m is None or ctx.peaks is None
            or "linear_num_value_heads" not in m or touched is None or not layer_steps):
        return None
    touched_a_step = touched / layer_steps * flops_qwen3_next.expert_layers(m)
    step_s = statistics.median(e - s for s, e in found) / 1e9 / steps
    need = flops_qwen3_next.decode_step_bytes(m, slots, touched_a_step)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / step_s
