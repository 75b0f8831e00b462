"""The share of ``setup_s``, in percent, that the program names: the
start-up phases of every process on the way to the window (agent and
worker, or the server; where two overlap the time counts once; the
unnamed time between two phases, the worker script's own code, does not
count) and the compile seconds after the last phase (the server's
programs, built on first requests; a worker's after its first step). The
rest is the benchmark's own traffic and waits (the canary, the admission
warm-up, ``warmup_seconds``, the warm-up cycles) or a hole in the spans."""

from benchmark.startup_records import load


def read(ctx):
    start = load(ctx)
    if start is None or ctx.setup_s <= 0:
        return None
    return 100.0 * start.named_s / ctx.setup_s
