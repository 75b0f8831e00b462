"""The busiest expert's rows over the mean of all 64, per expert layer and
decode step: the program's counters ``moe.load_max_over_mean`` (each
layer-step's ratio, summed) over ``moe.layer_steps`` (booked from the decode
chunk's own read-back), each taken as the difference between the window's two
``/healthz`` reads. 1 is an even load; the grouped products read an expert's
weights once however many rows chose it, so a high ratio means fewer experts
touched, not a slower step."""

from benchmark.program_spans import counter_in_window


def read(ctx):
    summed = counter_in_window(ctx.stamps, "moe.load_max_over_mean_n")
    layer_steps = counter_in_window(ctx.stamps, "moe.layer_steps_n")
    if summed is None or not layer_steps or layer_steps <= 0:
        return None
    return summed / layer_steps
