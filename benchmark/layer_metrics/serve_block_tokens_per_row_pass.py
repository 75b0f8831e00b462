"""Tokens fixed a live row and pass, over the window: the block chunk's
counters ``block.tokens_fixed`` over ``block.row_passes``
(``models/serving.py: make_block_chunk``; each the difference between the
window's two ``/healthz`` reads). A block of ``block_length`` positions takes
``denoising_steps`` passes that fix tokens and, as the engine runs it, one
that makes it final: 4 / 3 at block_length 4 and 2 steps, 2 once a final
pass rides with the next block's first. What an autoregressive server has
as 1 by construction."""

from benchmark.program_spans import counter_in_window


def read(ctx):
    fixed = counter_in_window(ctx.stamps, "block.tokens_fixed_n")
    row_passes = counter_in_window(ctx.stamps, "block.row_passes_n")
    if fixed is None or not row_passes or row_passes <= 0:
        return None
    return fixed / row_passes
