"""The head and the loss as a share of one train step's device time, in
percent: the operations under a ``<family>.head`` scope (the last norm, the
logits), ``loss.chunk`` (``layers.chunked_token_ce``'s scan) and
``train.loss``, forward and backward, over the step program's self time
(``benchmark/trace_scopes.py``)."""

from benchmark import trace_scopes


def read(ctx):
    return trace_scopes.share(trace_scopes.step_table(ctx), lambda tab: trace_scopes.scope_seconds(
        tab, lambda p: p.endswith(".head") or p in ("loss.chunk", "train.loss")))
