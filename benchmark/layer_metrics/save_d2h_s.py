"""Seconds one flash save spends waiting for the device-to-host copies:
the sum of the program's per-leaf ``ckpt.save.d2h`` spans (the
``np.asarray`` on a shard whose ``copy_to_host_async`` was kicked in
``ckpt.save.plan``) inside one ``ckpt.save``, as a mean over the faster
half of the traced window's saves. With ``save_memcpy_s`` and
``save_host_other_s`` it sums to that save's whole span."""

from benchmark.program_spans import save_part


def read(ctx):
    return save_part(ctx, lambda save: save["d2h_s"])
