"""What one ``span()`` costs, with and without a profiler session.

    python scripts/span_cost.py [--n 100000] [--trace-dir DIR]

Prints one JSON line of nanoseconds per call, each the best of three
loops of ``n``: the bare ``jax.profiler.TraceAnnotation``, ``span(name)``,
``span(name, step=i)`` and what the serving round did before (a pair of
``perf_counter`` reads and an ``add``), first with no session ("tracing
off"), then inside a ``jax.profiler`` session without the Python tracer
(as ``benchmark/reduce_trace.start_trace`` opens it). Run it where the
numbers are wanted: on the chip's host, ``chiprun -- python
scripts/span_cost.py``.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def best_ns(fn, n: int) -> float:
    return min(fn(n) for _ in range(3)) / n * 1e9


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=100_000)
    ap.add_argument("--trace-dir", default=None)
    ns = ap.parse_args()

    import jax

    from dlrover_tpu.observability.spans import SpanAccumulator

    acc = SpanAccumulator()

    def bare(n):
        t = time.perf_counter()
        for _ in range(n):
            with jax.profiler.TraceAnnotation("cost.bare"):
                pass
        return time.perf_counter() - t

    def plain(n):
        t = time.perf_counter()
        for _ in range(n):
            with acc.span("cost.span"):
                pass
        return time.perf_counter() - t

    def with_stat(n):
        t = time.perf_counter()
        for i in range(n):
            with acc.span("cost.span_stat", step=i):
                pass
        return time.perf_counter() - t

    def stamps(n):
        t = time.perf_counter()
        for _ in range(n):
            t0 = time.perf_counter()
            acc.add("cost.stamps", time.perf_counter() - t0)
        return time.perf_counter() - t

    cases = dict(annotation=bare, span=plain, span_with_stat=with_stat, perf_counter_pair_and_add=stamps)
    out = {"n": ns.n, "device": jax.devices()[0].device_kind,
           "off_ns": {k: round(best_ns(fn, ns.n), 1) for k, fn in cases.items()}}
    trace_dir = ns.trace_dir or tempfile.mkdtemp(prefix="span_cost_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        out["on_ns"] = {k: round(best_ns(fn, ns.n), 1) for k, fn in cases.items()}
    finally:
        jax.profiler.stop_trace()
        if ns.trace_dir is None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
