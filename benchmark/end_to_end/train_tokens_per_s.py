"""Tokens of the window's steps over the summed length of its step
segments. A segment runs from the return of one save (or sync) to
``block_until_ready`` on the state after the next N steps; time inside
saves is not in it."""

from benchmark.drivers.train_cycles import segment_rate


def read(ctx):
    return segment_rate(ctx.stamps)
