"""Starts the serving daemon for the benchmark: calls
``dlrover_tpu.launcher.serve.main`` with the arguments after ``--`` and
adds nothing to it but a control thread, because only the process that
holds the chip can trace it or read its memory.

    python3 benchmark/workers/serve_launcher.py --control <dir> -- <tpurun-serve arguments>

The parent writes ``<dir>/req_<n>.json`` ``{"cmd": ...}`` and reads
``<dir>/resp_<n>.json``. Commands: ``stats`` (device memory, and the
programs compiled or read from the cache so far, so that the parent can
see whether anything compiled inside its window), ``trace_start`` (with a
``dir``) and ``trace_stop`` (a ``live`` stop: the server decodes on, so the
window ends where the device's record does), whose answer carries
``reduce_trace.stop_trace``'s timings (``collect_s``, ``export_s``, ``xplane_bytes``, ``wrote``); the same
numbers go to this process's log as each half ends, so that a parent that
stops waiting finds in the log's tail how far the stop had come.
"""

import glob
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

COUNTS = {"cache_hits": 0, "cache_misses": 0, "compiles": 0}


def on_event(name, **_):
    if name == "/jax/compilation_cache/cache_hits":
        COUNTS["cache_hits"] += 1
    elif name == "/jax/compilation_cache/cache_misses":
        COUNTS["cache_misses"] += 1


def on_duration(name, *_, **__):
    if name == "/jax/core/compile/backend_compile_duration":
        COUNTS["compiles"] += 1


def say(text: str) -> None:
    print(f"bench-control: {text}", file=sys.stderr, flush=True)


def answer(cmd: dict) -> dict:
    import jax

    from benchmark import reduce_trace
    from benchmark.device_memory import memory_peak_bytes

    if cmd["cmd"] == "trace_start":
        reduce_trace.start_trace(cmd["dir"])
        return {"ok": True}
    if cmd["cmd"] == "trace_stop":
        say("trace_stop: begun")
        return dict(reduce_trace.stop_trace(log=say, live=True), ok=True)  # the server decodes on
    devices = jax.devices()[:1]
    return dict(COUNTS, ok=True, memory_peak_bytes=memory_peak_bytes(devices),
                memory_stats=devices[0].memory_stats() or {})


def control(directory: str) -> None:
    done = set()
    while True:
        for path in sorted(glob.glob(os.path.join(directory, "req_*.json"))):
            if path in done:
                continue
            done.add(path)
            try:
                with open(path) as f:
                    out = answer(json.load(f))
            except Exception as e:  # noqa: BLE001 — reported to the parent, which decides
                out = {"ok": False, "error": repr(e)[:300]}
            out["t"] = time.time()
            resp = path.replace("req_", "resp_")
            with open(resp + ".tmp", "w") as f:
                json.dump(out, f)
            os.replace(resp + ".tmp", resp)
        time.sleep(0.05)


def main() -> int:
    argv = sys.argv[1:]
    split = argv.index("--")
    assert argv[0] == "--control", "usage: serve_launcher.py --control <dir> -- <args>"
    directory = argv[1]
    os.makedirs(directory, exist_ok=True)
    from dlrover_tpu.launcher import serve

    import jax

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    threading.Thread(target=control, args=(directory,), daemon=True, name="bench-control").start()
    return serve.main(argv[split + 1:])


if __name__ == "__main__":
    sys.exit(main())
