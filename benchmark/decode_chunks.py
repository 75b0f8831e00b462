"""The decode chunk's executions in a serving trace, and what ran inside
them. A server's trace holds prefills, admissions and decode chunks one
after another on one device; a reader that wants the decode step's time
takes the executions of the chunk's program (the ``XLA Modules`` line, by
the configuration's ``trace_names.decode_chunk``) and the operation events
that lie inside them. Returns nothing where the trace or the pattern is
missing: another configuration's run, or a program without such a chunk.
"""

import bisect
import re


def executions(ctx):
    """[(start, end)] in ns of the chunk program's whole executions inside
    the traced window, sorted; None where there is nothing to read."""
    pattern = (ctx.config.get("trace_names") or {}).get("decode_chunk")
    if ctx.trace is None or not pattern or not ctx.trace.used_planes():
        return None
    lo, hi = ctx.trace.window
    plane = ctx.trace.first_plane()
    found = sorted((s, e) for s, e, name in ctx.trace.devices[plane]["modules"]
                   if s >= lo and e <= hi and re.search(pattern, name))
    return found or None


def op_seconds_inside(ctx, spans, pattern: str) -> float:
    """Device seconds of the operation events matching ``pattern`` that
    start inside one of the sorted, disjoint ``spans``."""
    starts = [s for s, _ in spans]
    total = 0.0
    for s, e, name in ctx.trace.devices[ctx.trace.first_plane()]["ops"]:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < spans[i][1] and re.search(pattern, name):
            total += (e - s) / 1e9
    return total


def steps_per_chunk(ctx):
    return (ctx.stamps.get("healthz") or {}).get("decode_chunk")


def traced_counter(ctx, key: str):
    """How far ``phase_split[key]`` moved between the two ``/healthz`` reads
    around the traced seconds (``stamps.phase_split_trace``)."""
    around = ctx.stamps.get("phase_split_trace")
    if not around or not around[0] or not around[1] or key not in around[1]:
        return None
    return around[1][key] - around[0].get(key, 0)
