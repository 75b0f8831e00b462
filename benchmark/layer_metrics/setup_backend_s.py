"""Seconds from the start of the chip-holding process (the training
worker, the server) to a live backend: the interpreter and the imports up
to JAX (``startup.imports``), then the PJRT client up to the first
``jax.devices()`` (``startup.backend``). A worker adopted from a warm spare
starts, for this, at the hand-off. From the start's own record
(``benchmark/startup_records.py``)."""

from benchmark.startup_records import phase_seconds


def read(ctx):
    return phase_seconds(ctx, "imports", "backend")
