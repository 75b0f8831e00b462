"""Useful row-steps over dispatched ones, in percent: tokens the engine
emitted over slots x chunk length summed over the chunks it dispatched
(the program's counters ``tokens_emitted`` and ``row_steps``, each taken as
the difference between the window's two ``/healthz`` reads). What is
missing from 100 is decode the device did for empty slots, for rows past
their cap or EOS, and for the tail of a chunk after a row finished."""

from benchmark.program_spans import counter_in_window


def read(ctx):
    emitted = counter_in_window(ctx.stamps, "tokens_emitted_n")
    dispatched = counter_in_window(ctx.stamps, "row_steps_n")
    if emitted is None or not dispatched or dispatched <= 0:
        return None
    return 100.0 * emitted / dispatched
