"""Output tokens that reached the client inside the window over the
window's length: every streamed line is stamped where the client reads it,
and its new tokens count if that moment lies inside the window. A request
that straddles an edge counts with the tokens on the inside (counting whole
requests where they completed put up to 4 x 128 tokens on either side of
each edge of a window of ~10.7k)."""

from benchmark.drivers.serve_closed import tokens_arrived


def read(ctx):
    n = tokens_arrived(ctx.stamps)
    return None if n is None else n / (ctx.stamps["t_close"] - ctx.stamps["t_open"])
