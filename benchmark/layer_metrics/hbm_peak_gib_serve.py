"""Peak device memory of the server, GiB (see ``hbm_peak_gib_train``)."""


def read(ctx):
    if "requests" not in ctx.stamps:
        return None
    return ctx.device["memory_peak_bytes"] / 2 ** 30
