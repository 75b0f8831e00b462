"""Process start of the benchmark command to the opening of the window:
the agent, JAX, the model build, compilation (or the cache read) and the
warm-up cycles."""


def read(ctx):
    return ctx.setup_s
