"""Of the decode steps' token-assignments, the share that landed on the
experts held here, in percent: the program's counters
``moe.assignments_here`` over ``moe.assignments_here`` +
``moe.assignments_absent`` (summed on the device inside the decode chunk,
booked from the chunk's own read-back), each taken as the difference
between the window's two ``/healthz`` reads. Where routing is even it is
``experts_held / num_experts``; what it leaves out of 100 is another chip's
to compute."""

from benchmark.program_spans import counter_in_window


def read(ctx):
    here = counter_in_window(ctx.stamps, "moe.assignments_here_n")
    absent = counter_in_window(ctx.stamps, "moe.assignments_absent_n")
    if here is None or absent is None or here + absent <= 0:
        return None
    return 100.0 * here / (here + absent)
