"""Median time to the first streamed token, send to first NDJSON line
with tokens, on the client's clock, over the requests sent and first
answered inside the window (a traced run's profiler runs after it). The
server streams by polling every 20 ms, so that is its grain."""

from benchmark.drivers.serve_closed import ttfts
from benchmark.harness import percentile


def read(ctx):
    values = ttfts(ctx.stamps)
    return percentile(values, 50) if values else None
