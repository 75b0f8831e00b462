"""How it was shown that ``reduce_trace.stop_trace``'s file is JAX's own
(on the chip, by hand, PR 55; ``test_reduce_trace.py`` runs it on the host,
at fewer executions):

    chiprun -- python3 benchmark/tests/compare_exports.py

One session over a few hundred executions of a small program is stopped
with ``session.stop()``; its bytes are written as ``stop_trace`` writes
them, and the same bytes go through ``session.export``, which is what
``jax.profiler.stop_trace`` (``stop_and_export``) does after collecting:
the ``xplane.pb`` and the ``trace.json.gz`` nothing here opens. Both files
are read by ``reduce_trace.load`` and compared event for event, with what
each way took. A second session, stopped by ``jax.profiler.stop_trace()``
itself, has to hold the same lines and as many events of the same names.
Run it again when JAX changes: where it prints ``same: False`` the
fallback in ``reduce_trace.stop_trace`` is the way to take.
"""

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

STEPS = 300


def main(steps: int = STEPS) -> int:
    import jax
    import jax.numpy as jnp
    from jax._src import profiler as jax_profiler

    from benchmark import reduce_trace

    out = os.path.join(ROOT, ".bench_work", "compare_exports")
    shutil.rmtree(out, ignore_errors=True)
    step = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    step(x).block_until_ready()

    def work():
        for _ in range(steps):
            with jax.profiler.TraceAnnotation("step_dispatch"):
                y = step(x)
            y.block_until_ready()

    ours, theirs, whole = (os.path.join(out, d) for d in ("ours", "theirs", "whole"))
    reduce_trace.start_trace(ours)
    work()
    session = jax_profiler._profile_state.profile_session
    timings = reduce_trace.stop_trace()
    t0 = time.time()
    with open(reduce_trace.find_xplane(ours), "rb") as f:
        session.export(f.read(), theirs)
    export_s = time.time() - t0
    reduce_trace.start_trace(whole)
    work()
    t0 = time.time()
    jax.profiler.stop_trace()
    whole_s = time.time() - t0

    a, b, c = (reduce_trace.load(d) for d in (ours, theirs, whole))
    spans = ["step_dispatch"]
    same = (a.devices == b.devices and a.host == b.host and a.window == b.window and a.events == b.events
            and a.breakdown(spans) == b.breakdown(spans) and a.busy_and_window(1) == b.busy_and_window(1))
    names = lambda t: sorted((p, k, len(v), len({n for _, _, n in v})) for p, d in t.devices.items() for k, v in d.items())  # noqa: E731
    print(jax.devices()[0].device_kind, "events", a.events, "busy_and_window", a.busy_and_window(1))
    print(f"written as stop_trace writes it: {timings}")
    print(f"session.export of the same bytes: {export_s:.3f} s, files", sorted(os.listdir(os.path.dirname(reduce_trace.find_xplane(theirs)))))
    print(f"same: {same}  (devices, host spans, window, counts, breakdown, busy: one file against the other)")
    print(f"jax.profiler.stop_trace() over the same work: {whole_s:.3f} s, events {c.events}")
    print(f"same lines and counts as a session stopped by JAX itself: {names(a) == names(c)} {names(a)}")
    shutil.rmtree(out, ignore_errors=True)
    return 0 if same and names(a) == names(c) else 1


if __name__ == "__main__":
    sys.exit(main())
