"""Model FLOP/s utilisation of the window / full attention decoder, in
percent: the operations a trained token requires, from the shapes as run
and the *counted* expert assignments (``benchmark/flops_mellum.py``: a
window layer's scores by the band's pairs, a full layer's by the causal
half, the routed products only for what landed on the experts held;
recomputed operations do not count), times the tokens/s of this (traced)
run's step segments, over chips x the bf16 peak of
``benchmark/peaks.json``."""

from benchmark import flops_mellum
from benchmark.drivers.train_cycles import segment_rate


def read(ctx):
    rate = segment_rate(ctx.stamps)
    counters = ctx.stamps.get("counters") or {}
    m = ctx.config.get("model", {}).get("config")
    if rate is None or ctx.peaks is None or m is None or not counters.get("moe.layer_steps"):
        return None
    tokens = ctx.stamps["tokens_per_step"]
    landed = counters["moe.assignments_here"] / (counters["moe.layer_steps"] * tokens)
    per_token = flops_mellum.train_flops_per_token(m, ctx.traffic["params"]["seq"], landed)
    return 100.0 * per_token * rate / (ctx.run.chips * ctx.peaks["bf16_flops_per_s"])
