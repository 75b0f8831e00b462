"""What moving rows to and from the experts costs of one train step's
device time, in percent: the operations under ``moe.route``,
``moe.dispatch`` and ``moe.combine`` (the router, the sorts, the gathers,
the weighted sum back), forward and backward, the grouped products
(``moe.experts``) not among them, over the step program's self time
(``benchmark/trace_scopes.py``)."""

from benchmark import trace_scopes

MOVES = ("moe.route", "moe.dispatch", "moe.combine")


def read(ctx):
    return trace_scopes.share(trace_scopes.step_table(ctx),
                              lambda tab: trace_scopes.scope_seconds(tab, lambda p: p in MOVES))
