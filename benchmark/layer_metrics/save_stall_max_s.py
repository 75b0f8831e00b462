"""The longest stall of any save in the window (the wrapper's stamps)."""

from benchmark.drivers.train_cycles import stalls


def read(ctx):
    got = stalls(ctx.stamps)
    return max(got) if got else None
