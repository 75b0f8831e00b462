"""90th percentile of the time to the first streamed token (see
``serve_ttft_p50_s``). A window of this length completes a few hundred
requests: enough for a p90, not for a p99."""

from benchmark.drivers.serve_closed import ttfts
from benchmark.harness import percentile


def read(ctx):
    values = ttfts(ctx.stamps)
    return percentile(values, 90) if values else None
