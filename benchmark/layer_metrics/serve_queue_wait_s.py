"""Mean seconds a request waits in the engine's queue for a free slot
(``submit_t`` to ``admit_t``): the program's counter ``queue_wait_s`` over
``requests_admitted``, each taken as the difference between the window's
two ``/healthz`` reads."""

from benchmark.program_spans import per_admitted_request


def read(ctx):
    return per_admitted_request(ctx.stamps, "queue_wait_s_sum")
