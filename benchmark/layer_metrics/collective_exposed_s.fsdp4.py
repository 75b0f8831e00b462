"""Seconds of one step in which a chip's core sits in a collective
(all-gather, reduce-scatter, all-reduce, collective-permute, all-to-all,
or the ``-done`` half of an asynchronous one) instead of computing: the
summed time of such operations on the trace's ``XLA Ops`` line, which is
the core's own sequence, so nothing else runs beside them; per step
execution, averaged over the chips. What an asynchronous collective moves
while compute runs is on another line and is not counted: that part is
hidden."""

import re

COLLECTIVE = re.compile(
    r" (all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)(-start|-done)?\(")


def read(ctx):
    if ctx.trace is None or "cycles" not in ctx.stamps or ctx.run.chips < 2:
        return None
    per_chip = []
    for plane in ctx.trace.used_planes(ctx.run.chips):
        _, steps = ctx.trace.main_module(plane)
        seconds = sum(v[0] for n, v in ctx.trace.op_seconds(plane).items() if COLLECTIVE.search(n))
        if steps:
            per_chip.append(seconds / len(steps))
    return sum(per_chip) / len(per_chip) if per_chip else None
