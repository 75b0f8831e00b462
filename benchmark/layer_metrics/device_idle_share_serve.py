"""Idle share of the server's chip over the traced seconds of the window,
in percent (the server's launcher brackets them with the profiler)."""


def read(ctx):
    if ctx.trace is None or "requests" not in ctx.stamps or not ctx.trace.used_planes():
        return None
    got = ctx.trace.busy_and_window(1)
    return 100.0 * (1.0 - got["busy_s"] / got["window_s"]) if got["window_s"] > 0 else None
