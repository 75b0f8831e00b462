"""The served tokens' required operations as a share of the chip's bf16
peak over the window, in percent: the share of the whole step, for a model
of Mamba-2 mixers and attention layers (``serve_mfu_share`` reads a model
with routed experts). For every token processed inside the window (a prompt
where its first token arrived inside; each streamed token at its own
context) two operations a multiply-add over the matrices a token meets, the
scan's block products or the one-token step, and attention over the real
context (``benchmark/flops_granite_hybrid.py``), over window x chips x
peak. Decode is bound by the bytes it moves, so this reads low; what it
leaves out of 100 is not idle time."""

from benchmark import flops_granite_hybrid


def read(ctx):
    requests = ctx.stamps.get("requests")
    m = (ctx.config.get("model") or {}).get("config")
    if requests is None or m is None or ctx.peaks is None or "mamba_n_heads" not in m:
        return None
    lo, hi = ctx.stamps["t_open"], ctx.stamps["t_close"]
    if hi <= lo or not any(r.get("prompt_len") is not None for r in requests):
        return None
    need = flops_granite_hybrid.window_flops(m, requests, lo, hi)
    return 100.0 * need / ((hi - lo) * ctx.run.chips * ctx.peaks["bf16_flops_per_s"])
