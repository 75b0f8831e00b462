"""Persistent XLA compilation cache: one placement rule for every process.

Recovery is compile-dominated once restore is overlapped: a restarted
(or re-meshed) worker re-traces and re-compiles the train step before
its first step runs. XLA's persistent compilation cache turns that into
a disk read — but only if every process of the job, and every later
run, points at the SAME directory: the directory is part of the cache
key, so a cache that moves never hits.

Placement (the whole rule):

- ``JAX_COMPILATION_CACHE_DIR`` set by the caller: JAX reads it itself,
  this code sets no other directory, and the agent hands the same value
  to its workers (:func:`resolve_cache_dir` in ``worker_env``).
- unset: one fixed path inside the checkout, :data:`DEFAULT_CACHE_DIR`
  (git-ignored). Never a path made from a temporary name, a pid or the
  time.

Turning the cache off is JAX's own switch too
(``JAX_ENABLE_COMPILATION_CACHE=0``; the chaos harnesses' cold arm sets
it). ``DLROVER_COMPILE_CACHE_MIN_COMPILE_S`` keeps kernel-sized entries
out. :func:`enable_compile_cache` is idempotent and must run before the
first compilation it should serve; a directory that cannot be made
raises — a cache silently off is an unexplained slow restart.

Compiles are measured, not inferred: :func:`watch_compiles` listens to
JAX's own monitoring events and books every program this process builds
into the process accumulator (``observability/spans.py``): seconds of
Python tracing, of MLIR lowering, of XLA compile (a miss) or of the read
from the persistent cache (a hit), and how many of each. It touches no
JAX config, so a server that sets its own cache options calls it alone.
"""

import collections
import logging
import os
import threading
import time
from typing import Dict, List, Optional

from ..observability.spans import process_accumulator
from .log import logger

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_ENABLE_ENV = "JAX_ENABLE_COMPILATION_CACHE"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_compile_cache",
)


def resolve_cache_dir() -> str:
    """The directory the placement rule yields in this environment.
    Imports no JAX — safe in the agent and in harness parents."""
    return os.environ.get(CACHE_DIR_ENV) or DEFAULT_CACHE_DIR


def enable_compile_cache(min_compile_s: Optional[float] = None) -> str:
    """Apply the placement rule to this process's jax config and return
    the directory in effect."""
    import jax

    from .config import get_context

    path = os.environ.get(CACHE_DIR_ENV)
    if not path:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != path:
            jax.config.update("jax_compilation_cache_dir", path)
            logger.info("persistent compile cache: %s", path)
    if min_compile_s is None:
        min_compile_s = get_context().compile_cache_min_compile_s
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_s)
    )
    watch_compiles()
    return path


# -- compiles, as JAX reports them ------------------------------------------

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_USES_CACHE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_HIT_EVENT = "/jax/compilation_cache/cache_hits"

COMPILE_RECORDS_KEPT = 64
# seconds by part, then counts: the keys of a record's ``compile`` totals
COMPILE_SECONDS = ("trace_s", "lower_s", "backend_s", "cache_read_s")
COMPILE_COUNTS = ("programs", "cache_hits", "cache_misses")

_watch_lock = threading.Lock()
_watching = False
_quiet = False
_records: "collections.deque[dict]" = collections.deque(
    maxlen=COMPILE_RECORDS_KEPT
)
_pending = threading.local()  # the program this thread is building


def watch_compiles(quiet_after_startup: bool = False) -> None:
    """Register the listeners, once a process. ``quiet_after_startup``: a
    program built after the start-up record was written is logged at INFO
    and not as a WARNING — the server's case, which builds its programs
    on first requests by design and logs every one."""
    global _watching, _quiet
    with _watch_lock:
        _quiet = _quiet or quiet_after_startup
        if _watching:
            return
        import jax

        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _watching = True


def watching() -> bool:
    return _watching


def _on_event(name: str, **_) -> None:
    # both fire inside ``compile_or_get_cached``, on the compiling thread,
    # before the backend duration that wraps it is reported
    if name == _USES_CACHE_EVENT:
        _pending.cache = "miss"  # until the read finds it
    elif name == _HIT_EVENT:
        _pending.cache = "hit"


def _on_duration(name: str, dur_s: float, **kw) -> None:
    if name == _TRACE_EVENT:
        # Every jitted function traced reports, the ones a function calls
        # before the function itself: an event that covers earlier ones
        # replaces them, so the list holds whole traces only. A trace may
        # be far older than its program (``eval_shape`` first, the call
        # later finds the trace cached): it waits here by name.
        now = time.time()
        traces = getattr(_pending, "traces", None)
        if traces is None:
            traces = _pending.traces = []
        began = now - dur_s
        while traces and traces[-1][0] >= began:
            traces.pop()
        traces.append((began, now, kw.get("fun_name", ""), dur_s))
    elif name == _LOWER_EVENT:
        module = kw.get("fun_name", "")
        began = time.time() - dur_s
        traces = getattr(_pending, "traces", None) or []
        # what was traced since lowering began are the lowering rules' own
        # small functions (thousands for a large model): not programs
        while traces and traces[-1][1] > began + 1e-3:
            traces.pop()
        trace_s = 0.0
        for i in range(len(traces) - 1, -1, -1):
            if module.endswith(f"({traces[i][2]})"):
                trace_s += traces[i][3]  # a cached trace's own event reads ~0
                del traces[i]
        del traces[:-32]  # traced and never lowered (``eval_shape``): bounded
        _pending.lowered = (module, trace_s, dur_s)
    elif name == _BACKEND_EVENT:
        _finish_program(kw.get("fun_name", ""), dur_s)


def _finish_program(fun_name: str, backend_s: float) -> None:
    """The backend duration closes a program: ``compile_or_get_cached``
    returned, with a compile or with a read. Trace and lowering seconds
    are booked only here, so a function that was lowered and never
    compiled (``.lower().as_text()``) counts for nothing."""
    lowered = getattr(_pending, "lowered", None)
    cache = getattr(_pending, "cache", None) or "off"
    _pending.lowered = _pending.cache = None
    trace_s = lower_s = 0.0
    if lowered is not None and lowered[0] == fun_name:
        _, trace_s, lower_s = lowered
    record = {
        "fun_name": fun_name,
        # when it reached the backend: tracing and lowering came before,
        # not always right before (a lowering is cached too)
        "unix_ns": time.time_ns() - int(backend_s * 1e9),
        "trace_s": round(trace_s, 6),
        "lower_s": round(lower_s, 6),
        "backend_s": round(backend_s, 6),
        "cache": cache,
        "thread": threading.current_thread().name,
    }
    acc = process_accumulator()
    acc.count("compile.trace_s", trace_s)
    acc.count("compile.lower_s", lower_s)
    acc.count("compile.programs")
    if cache == "hit":
        acc.count("compile.cache_read_s", backend_s)
        acc.count("compile.cache_hits")
    else:
        acc.count("compile.backend_s", backend_s)
        if cache == "miss":
            acc.count("compile.cache_misses")
    _records.append(record)
    if acc.startup_closed:
        _after_startup(record)
    elif _quiet:
        logger.info("compiled: %s", describe_compile(record))


def describe_compile(record: dict) -> str:
    return (
        f"{record['fun_name']} trace {record['trace_s']:.3f} s lower "
        f"{record['lower_s']:.3f} s backend {record['backend_s']:.3f} s "
        f"({record['cache']})"
    )


def _after_startup(record: dict) -> None:
    """A program built after the start's record was written: "which step
    recompiled". One log line, and one small record where a spool is set,
    so that whoever reads the spool can place it in time."""
    from ..attribution.recovery import record_phase_file

    logger.log(
        logging.INFO if _quiet else logging.WARNING,
        "compiled after start-up: %s", describe_compile(record),
    )
    record_phase_file("compile", dict(record, pid=os.getpid()))


def compile_totals() -> Dict[str, float]:
    """Seconds and counts of every program built so far in this process."""
    counters = process_accumulator().counters()
    return {
        key: round(counters.get("compile." + key, 0), 6)
        for key in COMPILE_SECONDS + COMPILE_COUNTS
    }


def compile_seconds_since(unix_ns: int) -> float:
    """Seconds (tracing, lowering, compile or cache read) of the programs
    among the last 64 that reached the backend at or after ``unix_ns``:
    what the programs a span of time asked for cost to make."""
    return sum(
        r["trace_s"] + r["lower_s"] + r["backend_s"]
        for r in compile_records()
        if r["unix_ns"] >= unix_ns
    )


def compile_records() -> List[dict]:
    """The last programs built, oldest first (at most 64)."""
    return list(_records)
