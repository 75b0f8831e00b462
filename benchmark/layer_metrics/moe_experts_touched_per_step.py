"""Distinct experts that at least one row chose, per expert layer and decode
step, of ``num_experts``: the program's counters ``moe.experts_touched``
over ``moe.layer_steps`` (booked from the decode chunk's own read-back),
each taken as the difference between the window's two ``/healthz`` reads.
The bytes a decode step reads follow this number."""

from benchmark.program_spans import counter_in_window


def read(ctx):
    touched = counter_in_window(ctx.stamps, "moe.experts_touched_n")
    layer_steps = counter_in_window(ctx.stamps, "moe.layer_steps_n")
    if touched is None or not layer_steps or layer_steps <= 0:
        return None
    return touched / layer_steps
