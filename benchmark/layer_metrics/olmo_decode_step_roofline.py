"""The whole decode step of the delta-rule / full-attention server against
its roofline, in percent, whatever implements it: the bytes one step has to
move (the matrices every step reads once; each row's keys and values **at
its real length**, by the engine's ``kv_positions_valid`` over the traced
seconds' decoded steps, not the ``slots x max_seq_len`` the dense cache
holds; every slot's matrix state and convolution inputs once in and once
out: ``benchmark/flops_olmo_hybrid.py``) over the chip's HBM rate, over the
device seconds of a step (the decode chunk program's median execution over
its steps, what ``serve_decode_step_device_s`` reads). A step that reads
every row whole pays for ``serve_kv_valid_share``'s complement here."""

import statistics

from benchmark import decode_chunks, flops_olmo_hybrid


def read(ctx):
    found = decode_chunks.executions(ctx)
    steps = decode_chunks.steps_per_chunk(ctx)
    m = (ctx.config.get("model") or {}).get("config")
    slots = (ctx.stamps.get("healthz") or {}).get("slots")
    valid = decode_chunks.traced_counter(ctx, "kv_positions_valid_n")
    row_steps = decode_chunks.traced_counter(ctx, "row_steps_n")
    if (not found or not steps or not slots or m is None or ctx.peaks is None
            or "linear_allow_neg_eigval" not in m or valid is None or not row_steps):
        return None
    valid_a_step = valid / (row_steps / slots)  # the rows' real lengths, summed, at a mean step
    step_s = statistics.median(e - s for s, e in found) / 1e9 / steps
    need = flops_olmo_hybrid.decode_step_bytes(m, slots, valid_a_step)
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / step_s
