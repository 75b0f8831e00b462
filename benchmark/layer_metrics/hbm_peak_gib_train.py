"""Peak device memory of the training worker on its fullest chip, GiB:
``peak_bytes_reserved`` plus the live ``bytes_in_use`` after the window
(XLA's reservation for the step is not in ``peak_bytes_in_use``)."""


def read(ctx):
    if "cycles" not in ctx.stamps:
        return None
    return ctx.device["memory_peak_bytes"] / 2 ** 30
