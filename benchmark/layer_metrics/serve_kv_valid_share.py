"""Of the key/value positions the decode steps of the window held (every
slot's whole row, ``max_seq_len`` positions, each step), the share that the
rows' real lengths needed, in percent: the engine's counters
``kv_positions_valid`` / ``kv_positions_held`` (booked at each chunk's
read-back: a step that emits a row's m-th token needs its prompt and those
m tokens). What a decode step that reads a row whole reads in vain is the
rest."""

from benchmark.program_spans import counter_in_window


def read(ctx):
    held = counter_in_window(ctx.stamps, "kv_positions_held_n")
    valid = counter_in_window(ctx.stamps, "kv_positions_valid_n")
    if valid is None or not held or held <= 0:
        return None
    return 100.0 * valid / held
