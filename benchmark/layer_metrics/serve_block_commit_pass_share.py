"""Of the window's passes over live rows, the share that fixed nothing, in
percent: the block chunk's counters ``block.commit_row_passes`` over
``block.row_passes``. Such a pass was given a block with every position
decided and wrote its final tokens' keys and values: a third of all at 2
denoising steps, and what carrying a final pass with the next block's first
would take off the path."""

from benchmark.program_spans import counter_in_window


def read(ctx):
    commits = counter_in_window(ctx.stamps, "block.commit_row_passes_n")
    row_passes = counter_in_window(ctx.stamps, "block.row_passes_n")
    if commits is None or not row_passes or row_passes <= 0:
        return None
    return 100.0 * commits / row_passes
