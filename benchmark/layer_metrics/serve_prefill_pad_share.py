"""The share of prefilled positions that were padding, in percent, over the
window: 1 - ``prefill_tokens_real`` / ``prefill_tokens_padded`` (the
engine's counters, summed at admission: a prompt's own length against its
bucket's width). A scan, unlike attention, pays for every padded position
of a bucket."""


from benchmark.program_spans import counter_in_window


def read(ctx):
    padded = counter_in_window(ctx.stamps, "prefill_tokens_padded_n")
    real = counter_in_window(ctx.stamps, "prefill_tokens_real_n")
    if real is None or not padded or padded <= 0:
        return None
    return 100.0 * (1.0 - real / padded)
