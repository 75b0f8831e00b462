"""Idle share of the server's chip over the traced seconds of the window,
in percent (the server's launcher brackets them with the profiler). Since
PR 55 the window is ``live`` (``reduce_trace.Trace``): from the first
program begun a quarter second after the opening marker to the end of the
device's record, not from marker to marker. The program in flight at the
stop left up to a whole chunk unrecorded, and that one gap was most of
what this read before; the first launches after the profiler's start
stall, which was most of the rest (``PERF.md`` section 6, PR 55: no reading of PR 53 or
earlier compares with one of PR 55 or later)."""


def read(ctx):
    if ctx.trace is None or "requests" not in ctx.stamps or not ctx.trace.used_planes():
        return None
    got = ctx.trace.busy_and_window(1)
    return 100.0 * (1.0 - got["busy_s"] / got["window_s"]) if got["window_s"] > 0 else None
