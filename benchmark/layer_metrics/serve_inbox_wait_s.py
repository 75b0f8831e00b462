"""Mean seconds a request waits in the daemon's inbox before the engine
sees it: the driver thread picks requests up only between two
``engine.step`` calls. The program's counter ``inbox_wait_s`` (stamped on
the caller's thread in ``ServingDaemon._submit_item``, closed at
``engine.submit``) over ``requests_admitted``, each taken as the
difference between the window's two ``/healthz`` reads."""

from benchmark.program_spans import per_admitted_request


def read(ctx):
    return per_admitted_request(ctx.stamps, "inbox_wait_s_sum")
