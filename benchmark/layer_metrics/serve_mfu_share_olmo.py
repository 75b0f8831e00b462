"""The served tokens' required operations as a share of the chip's bf16
peak over the window, in percent: the share of the whole, for a model of
gated delta-rule mixers, ungrouped attention and a dense SwiGLU served at
long prompts. For every token processed inside the window (a prompt where
its first token arrived inside; each streamed token at its own context) two
operations a multiply-add over the matrices a token meets, the chunked form
or the one-token step, and attention over the real context
(``benchmark/flops_olmo_hybrid.py``), over window x chips x peak. Padding
is not required work, and decode is bound by the bytes it moves: what this
leaves out of 100 is not idle time."""

from benchmark import flops_olmo_hybrid


def read(ctx):
    requests = ctx.stamps.get("requests")
    m = (ctx.config.get("model") or {}).get("config")
    if requests is None or m is None or ctx.peaks is None or "linear_allow_neg_eigval" not in m:
        return None
    lo, hi = ctx.stamps["t_open"], ctx.stamps["t_close"]
    if hi <= lo or not any(r.get("prompt_len") is not None for r in requests):
        return None
    need = flops_olmo_hybrid.window_flops(m, requests, lo, hi)
    return 100.0 * need / ((hi - lo) * ctx.run.chips * ctx.peaks["bf16_flops_per_s"])
