"""The served window's required operations as a share of the chip's bf16
peak over the window, in percent: the share of the whole, for a model served
by diffusion over blocks. Every prompt whose first block arrived inside the
window at its real prefilled tokens, and the window's passes at
``block_length`` positions a live row reading the rows' real lengths (the
engine's ``block.row_passes`` and ``kv_positions_valid`` between the window's
two ``/healthz`` reads: ``benchmark/flops_sdar_moe.py: window_flops``), over
window x chips x peak. A pass is bound by the bytes it moves: what this leaves
out of 100 is not idle time."""

from benchmark import flops_sdar_moe
from benchmark.program_spans import counter_in_window


def read(ctx):
    requests = ctx.stamps.get("requests")
    m = (ctx.config.get("model") or {}).get("config")
    row_passes = counter_in_window(ctx.stamps, "block.row_passes_n")
    valid = counter_in_window(ctx.stamps, "kv_positions_valid_n")
    if requests is None or m is None or ctx.peaks is None or "block_length" not in m or not row_passes or valid is None:
        return None
    lo, hi = ctx.stamps["t_open"], ctx.stamps["t_close"]
    if hi <= lo:
        return None
    need = flops_sdar_moe.window_flops(m, requests, lo, hi, row_passes, valid)
    return 100.0 * need / ((hi - lo) * ctx.run.chips * ctx.peaks["bf16_flops_per_s"])
