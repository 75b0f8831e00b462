"""Model FLOP/s utilisation in percent: 6 N + 12 L d T operations a token
(``benchmark/flops.py``; recomputed operations do not count) times the
tokens/s of this (traced) run's step segments, over chips x the bf16 peak
of ``benchmark/peaks.json``."""

from benchmark import flops
from benchmark.drivers.train_cycles import segment_rate


def read(ctx):
    rate = segment_rate(ctx.stamps)
    if rate is None or ctx.peaks is None:
        return None
    g = ctx.config["gpt_config"]
    per_token = flops.train_flops_per_token(
        g["num_layers"], g["embed_dim"], g["vocab_size"], ctx.traffic["params"]["seq"],
        g.get("mlp_ratio", 4))
    return 100.0 * per_token * rate / (ctx.run.chips * ctx.peaks["bf16_flops_per_s"])
