"""The forward pass's share of one train step's device time, in percent:
the self time of the step program's operations (``Trace.main_module``'s
executions, as ``step_device_s``) whose ``op_name`` holds neither
``transpose(`` nor an update scope, over all of it
(``benchmark/trace_scopes.py``). A share of one step: it describes, and
``higher`` is only what a reader would want with the other two standing
still."""

from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.share(trace_scopes.step_table(ctx), lambda tab: tab["passes"]["forward"])
