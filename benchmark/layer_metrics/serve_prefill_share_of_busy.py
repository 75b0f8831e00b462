"""The prefill programs' share of the device's busy time over the traced
seconds, in percent: the executions of the programs named by the
configuration's ``trace_names.prefill`` (the ``XLA Modules`` line), clipped
to the window, over the union of the device's operations there. 0 where
the traced seconds held no prefill; nothing where the configuration names
no such program."""

import re

from benchmark import reduce_trace


def read(ctx):
    pattern = (ctx.config.get("trace_names") or {}).get("prefill")
    if ctx.trace is None or not pattern or "requests" not in ctx.stamps or not ctx.trace.used_planes():
        return None
    lo, hi = ctx.trace.window
    plane = ctx.trace.first_plane()
    busy = reduce_trace.total(reduce_trace.clip(ctx.trace.busy(plane), lo, hi))
    if busy <= 0:
        return None
    prefills = reduce_trace.union(
        (s, e) for s, e, name in ctx.trace.devices[plane]["modules"] if re.search(pattern, name))
    return 100.0 * reduce_trace.overlap(ctx.trace.busy(plane), reduce_trace.clip(prefills, lo, hi)) / busy
