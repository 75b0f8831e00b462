"""What of one train step's device time the program can name, in percent:
100 less the operations with no ``op_name`` or none that holds a scope or a
flax module (copies, layout changes, what XLA made of several: ``unscoped``,
by opcode in ``device_scopes.json``) and less those the trace's record of
the compiled program does not hold (``benchmark/trace_scopes.py``)."""

from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.share(trace_scopes.step_table(ctx), trace_scopes.scoped_seconds)
