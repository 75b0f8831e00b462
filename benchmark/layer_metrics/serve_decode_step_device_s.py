"""Device seconds of one decode step: the median whole execution of the
decode chunk's program in the traced seconds (``trace_names.decode_chunk``)
over the steps a chunk holds (``/healthz``'s ``decode_chunk``). A step
decodes one token for every slot, live or not."""

import statistics

from benchmark import decode_chunks


def read(ctx):
    found = decode_chunks.executions(ctx)
    steps = decode_chunks.steps_per_chunk(ctx)
    if not found or not steps:
        return None
    return statistics.median(e - s for s, e in found) / 1e9 / steps
