"""Seconds the chip waits for one flash checkpoint: from the moment the
state of the save's step is ready on the device (the sync that ends the
segment) to the return of ``engine.save_to_memory``, on the worker's clock.

The statistic is the mean of the *faster half* of the window's saves. Two
or three saves of ten take 6-7 s instead of 0.5 s and the one after each
1.1 s (PERF.md, PR 23), so the mean over all ten swings by a third with how
many of those the window held, and the median by 2-7%; the faster half is
the save when nothing else gets in its way, and read within 2.3% over six
runs. A checkpoint that gets slower or faster moves it one for one. The
slow saves are what ``save_stall_max_s`` and ``save_share_of_window`` show;
the whole series is in the result line under ``checks.save_stalls_s``."""

from benchmark.drivers.train_cycles import faster_half_mean, stalls


def read(ctx):
    got = stalls(ctx.stamps)
    return faster_half_mean(got) if got else None
