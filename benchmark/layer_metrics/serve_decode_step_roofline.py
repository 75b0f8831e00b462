"""The whole decode step against its roofline, in percent, whatever
implements it: the bytes one step has to move (the held parameters once;
every slot's recurrent state and convolution inputs once in and once out;
the dense keys and values once: ``benchmark/flops_granite_hybrid.py``) over
the chip's HBM rate, over the device seconds of a step (the decode chunk
program's median execution over its steps, what
``serve_decode_step_device_s`` reads)."""

import statistics

from benchmark import decode_chunks, flops_granite_hybrid


def read(ctx):
    found = decode_chunks.executions(ctx)
    steps = decode_chunks.steps_per_chunk(ctx)
    m = (ctx.config.get("model") or {}).get("config")
    slots = (ctx.stamps.get("healthz") or {}).get("slots")
    if not found or not steps or not slots or m is None or ctx.peaks is None or "mamba_n_heads" not in m:
        return None
    step_s = statistics.median(e - s for s, e in found) / 1e9 / steps
    return 100.0 * flops_granite_hybrid.decode_step_bytes(m, slots) / ctx.peaks["hbm_bytes_per_s"] / step_s
