"""Mean seconds from a request's admission to the host's first sight of a
token of it (``admit_t`` to ``first_tok_t``: the rest of the round that
admitted it, the chunk that decodes it and the sync that reads it back):
the program's counter ``admit_to_first_token_s`` over
``requests_admitted``, each taken as the difference between the window's
two ``/healthz`` reads."""

from benchmark.program_spans import per_admitted_request


def read(ctx):
    return per_admitted_request(ctx.stamps, "admit_to_first_token_s_sum")
