"""How uneven the held experts' load is: the busiest held expert's
assignments over the mean of the experts held, per expert layer and step,
averaged over the window (the program's ``moe.load_max_over_mean``
counter over ``moe.layer_steps``). 1 is even."""


def read(ctx):
    counters = ctx.stamps.get("counters") or {}
    if not counters.get("moe.layer_steps"):
        return None
    return counters["moe.load_max_over_mean"] / counters["moe.layer_steps"]
