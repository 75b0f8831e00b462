"""The expert layers' share of the decode chunk's device time, in percent:
the operations under ``moe.*`` (router, row movement, grouped products, the
shared expert's gate), over the self time of all operations inside the
chunk program's executions. A row of the table goes to the innermost of its
path's components that one of the four kinds accepts
(``trace_scopes.DECODE_PARTS``)."""

from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.share(trace_scopes.decode_table(ctx),
                              lambda tab: trace_scopes.decode_part_seconds(tab, "moe"))
