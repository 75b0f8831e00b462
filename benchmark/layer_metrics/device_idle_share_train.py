"""Idle share of the device *inside step segments* only, in percent: the
traced window less the host's ``save_call`` spans, and of that the part
in which no operation ran. What the loop, the dispatch and the input
pipeline cost the chip; the saves' share is ``save_share_of_window``."""

from benchmark.reduce_trace import gaps, union


def read(ctx):
    if ctx.trace is None or "cycles" not in ctx.stamps or not ctx.trace.used_planes():
        return None
    lo, hi = ctx.trace.window
    segments = gaps(union(ctx.trace.host.get("save_call", [])), lo, hi)
    share = ctx.trace.idle_share_inside(segments)
    return None if share is None else 100.0 * share
