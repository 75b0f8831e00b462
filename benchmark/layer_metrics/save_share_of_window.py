"""Summed save stall over the window's wall time, in percent, at this
cell's cadence. It is a property of the cadence as much as of the
checkpoint layer: a save every 10 steps is far denser than a deployment's."""

from benchmark.drivers.train_cycles import stalls


def read(ctx):
    got = stalls(ctx.stamps)
    if not got:
        return None
    return 100.0 * sum(got) / (ctx.stamps["cycles"][-1]["t_ret"] - ctx.stamps["t_open"])
