"""Attention's share of the decode chunk's device time, in percent: the
operations under a ``<family>.attn`` scope (an attention sublayer: its norm
where the block has it there, its projections, and inside them
``<family>.attend*``: the rotation, the product over the row's keys and
values, the output projection) or a flax attention module
(``CausalSelfAttention_0``), the cache's scatter not among them, over the self time of all operations inside the chunk program's
executions. A row of the table goes to the innermost of its path's
components that one of the four kinds accepts
(``trace_scopes.DECODE_PARTS``), so the four never count a row twice."""

from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.share(trace_scopes.decode_table(ctx),
                              lambda tab: trace_scopes.decode_part_seconds(tab, "attend"))
