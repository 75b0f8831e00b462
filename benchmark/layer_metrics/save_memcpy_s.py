"""Seconds one flash save spends copying host arrays into the ``/dev/shm``
segment: the sum of the program's per-leaf ``ckpt.save.memcpy`` spans
inside one ``ckpt.save``, as a mean over the faster half of the traced
window's saves."""

from benchmark.program_spans import save_part


def read(ctx):
    return save_part(ctx, lambda save: save["memcpy_s"])
