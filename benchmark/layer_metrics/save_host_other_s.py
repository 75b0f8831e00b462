"""What is left of one flash save beside the per-leaf waits and copies:
the program's whole ``ckpt.save`` span less its ``ckpt.save.d2h`` and
``ckpt.save.memcpy`` spans, so the prefetch join, the shard lock and the
all-hosts allgather (``ckpt.save.ready``), the flatten, the records and
the copy kicks (``ckpt.save.plan``), the segment (``ckpt.save.ensure``)
and the loop between leaves; mean over the faster half of the traced
window's saves."""

from benchmark.program_spans import save_part


def read(ctx):
    return save_part(ctx, lambda save: save["whole_s"] - save["d2h_s"] - save["memcpy_s"])
