"""The served tokens' required operations as a share of the chip's bf16
peak over the window, in percent: the share of the whole step, for a model
of gated delta-rule mixers, gated attention and routed experts of which a
share lives here. For every token processed inside the window (a prompt
where its first token arrived inside; each streamed token at its own
context) two operations a multiply-add over the matrices a token meets
(the routed experts by the *counted* mean of a token's assignments that
landed here: the chunk's counters over the window, every slot's row; the
even-routing mean where the program has no such counter), the chunked form
or the one-token step, and attention over the real context
(``benchmark/flops_qwen3_next.py``), over window x chips x peak. Decode is
bound by the bytes it moves, so this reads low; what it leaves out of 100
is not idle time."""

from benchmark import flops_qwen3_next
from benchmark.program_spans import counter_in_window


def read(ctx):
    requests = ctx.stamps.get("requests")
    m = (ctx.config.get("model") or {}).get("config")
    if requests is None or m is None or ctx.peaks is None or "linear_num_value_heads" not in m:
        return None
    lo, hi = ctx.stamps["t_open"], ctx.stamps["t_close"]
    if hi <= lo or not any(r.get("prompt_len") is not None for r in requests):
        return None
    here = flops_qwen3_next.mean_assignments_here(m)
    landed = counter_in_window(ctx.stamps, "moe.assignments_here_n")
    layer_steps = counter_in_window(ctx.stamps, "moe.layer_steps_n")
    slots = (ctx.stamps.get("healthz") or {}).get("slots")
    if landed is not None and layer_steps and slots:
        here = landed / (layer_steps * slots)
    need = flops_qwen3_next.window_flops(m, requests, lo, hi, here)
    return 100.0 * need / ((hi - lo) * ctx.run.chips * ctx.peaks["bf16_flops_per_s"])
