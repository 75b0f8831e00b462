"""The backward pass's share of one train step's device time, in percent:
the operations whose ``op_name`` holds ``transpose(``, a rematerialised
forward among them (it runs there and is counted there), over the step
program's self time (``benchmark/trace_scopes.py``). A share of one step:
``lower`` is only what a reader would want with the other two standing
still."""

from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.share(trace_scopes.step_table(ctx), lambda tab: tab["passes"]["backward"])
