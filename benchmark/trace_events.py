"""Operation events of a trace by the configuration's own patterns. A
device event's name is the whole HLO instruction (result type, operands,
attributes), so a pattern finds a kernel by the scope it was traced under
and a reader can take a call's sizes from the event's own text."""

import re


def matching(ctx, name: str):
    """[(device seconds, the event's text)] of the first device's operation
    events that lie inside the traced window and match the configuration's
    ``trace_names[name]``; None where there is no trace or no such pattern."""
    pattern = (ctx.config.get("trace_names") or {}).get(name)
    if ctx.trace is None or not pattern or not ctx.trace.used_planes():
        return None
    lo, hi = ctx.trace.window
    return [((e - s) / 1e9, text) for s, e, text in ctx.trace.devices[ctx.trace.first_plane()]["ops"]
            if s >= lo and e <= hi and re.search(pattern, text)]
