"""The update's share of one train step's device time, in percent: the
operations under ``train.optimizer`` (the clip and ``optax``'s update, the
parameters' new values), ``train.grad_norm`` and ``train.accumulate``, over
the step program's self time (``benchmark/trace_scopes.py``). A share of
one step: ``lower`` is only what a reader would want with the other two
standing still."""

from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.share(trace_scopes.step_table(ctx), lambda tab: tab["passes"]["update"])
