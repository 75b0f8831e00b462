"""The server's own account of its host share inside the window, in
percent: host phases over all phases, each taken as the difference between
the ``/healthz`` ``phase_split`` totals read at the window's two ends
(server-side host clock; warm-up and canary are before the first read).
The phase names are the engine's (``attribution/phases.py``): admission,
decode dispatch and retirement are the host's, prefill and the blocking
fetch the device's, ``overlap_hidden`` host work behind a running chunk,
which counts in the total only."""

HOST_PHASES = ("admission_ms", "decode_dispatch_ms", "retirement_ms")


def read(ctx):
    first = ctx.stamps.get("phase_split_open")
    last = (ctx.stamps.get("healthz") or {}).get("phase_split")
    if not first or not last:
        return None
    spent = {k: last[k] - first.get(k, 0.0) for k in last if k.endswith("_ms")}
    total = sum(spent.values())
    if total <= 0:
        return None
    return 100.0 * sum(spent.get(k, 0.0) for k in HOST_PHASES) / total
