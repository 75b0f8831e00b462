"""Hot-path spans on the profiler's clock, and the totals they leave.

``span(name, **stats)`` is the one primitive the program's hot paths use
to name their own time (the flash save, the train loop, the serving
round):

- it opens a ``jax.profiler.TraceAnnotation(name, **stats)``, so the span
  lands in the same ``.xplane.pb`` as the device planes of whatever
  profiler session is running — one clock for host and device. With no
  session the annotation is inert: that is "tracing off". Parent and
  child are containment on one thread's line; a span caused from another
  thread carries the cause as a stat (``uid=``, ``step=``);
- on exit it books its duration into a :class:`SpanAccumulator` by name
  (total, self time, count, max, log2-µs histogram), which is what
  ``/healthz`` and tests read with no profiler at all.

Hot-path spans do NOT go through ``common.events.EventEmitter``: an
``Event`` costs a ``uuid4``, a dict and a locked ring append, and
steady-state rounds would flush the flight recorder's ring, which exists
to hold the last events before a death. Incident spans (``ckpt_save``,
``rendezvous``) keep ``DurationSpan``, which opens an annotation of its
own name through :func:`annotation` when used as a ``with`` block.

Start-up phases use the same primitive (:func:`startup_span`): a span
named ``startup.<phase>`` whose begin (``unix_ns``) and length are also
kept, one record a phase, so that a start can be laid out on one clock
with the starts of other processes (``attribution/recovery.py`` writes
the record). They run once a start and never in a loop.

This module imports nothing heavy: the agent, the master and the
launcher never import JAX (the chip belongs to the worker), so the
annotation class is looked up only in a process that already has.
"""

import functools
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional

# log2(µs) histogram: bucket i covers [2^i, 2^(i+1)) µs; 20 buckets
# reach ~10 min — far past any sane span.
HIST_BUCKETS = 20


@dataclass
class SpanStat:
    total_s: float = 0.0  # inclusive: whole durations
    self_s: float = 0.0  # less what child spans of the same accumulator cover
    count: int = 0
    max_s: float = 0.0
    hist: List[int] = field(default_factory=lambda: [0] * HIST_BUCKETS)


def _hist_bucket(dur_s: float) -> int:
    us = int(dur_s * 1e6)
    if us < 1:
        return 0
    return min(us.bit_length() - 1, HIST_BUCKETS - 1)  # floor(log2(us))


_trace_annotation = None


def annotation(name: str, **stats):
    """A ``jax.profiler.TraceAnnotation`` (not yet entered), or None in a
    process that has not imported JAX — this never imports it."""
    global _trace_annotation
    cls = _trace_annotation
    if cls is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        cls = getattr(profiler, "TraceAnnotation", None)
        if cls is None:
            return None
        _trace_annotation = cls
    return cls(name, **stats)


class _Span:
    """One open span: a context manager for one ``with`` on one thread."""

    __slots__ = ("_acc", "_key", "_ann", "_t0", "_child_s", "_parent")

    def __init__(self, acc: "SpanAccumulator", name: str, key, stats: dict):
        self._acc = acc
        self._key = key
        self._ann = annotation(name, **stats)
        self._child_s = 0.0

    def set(self, **stats) -> None:
        """Stats known only once the work is under way (``bytes=``)."""
        if self._ann is not None:
            self._ann.set_metadata(**stats)

    def __enter__(self) -> "_Span":
        local = self._acc._local
        self._parent = getattr(local, "open", None)
        local.open = self
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        dur_s = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self._acc._local.open = self._parent
        if self._parent is not None:
            self._parent._child_s += dur_s
        if self._key:
            self._acc._book(self._key, dur_s, dur_s - self._child_s)


class _StartupSpan(_Span):
    """A start-up phase: a span that also leaves ``{name, unix_ns, s}`` in
    its accumulator's list of phases."""

    __slots__ = ("_unix_ns",)

    def __init__(self, acc: "SpanAccumulator", name: str, stats: dict):
        self._unix_ns = time.time_ns()
        super().__init__(acc, name, name, dict(stats, unix_ns=self._unix_ns))

    def __enter__(self) -> "_StartupSpan":
        self._acc._local.startup_open = True
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        dur_s = time.perf_counter() - self._t0
        super().__exit__(exc_type, exc, tb)
        self._acc._local.startup_open = False
        self._acc._keep_phase(self._key, self._unix_ns, dur_s)


class SpanAccumulator:
    """Running per-name totals of spans, and plain counters. Booking is a
    few dict ops under a lock — cheap enough to leave always-on (a few
    calls per scheduler round or per checkpoint leaf, never per token)."""

    def __init__(self):
        self._stats: Dict[str, SpanStat] = {}
        self._counters: Dict[str, float] = {}
        self._lock = threading.Lock()
        self._local = threading.local()  # the innermost open span per thread
        # start-up phases in the order they ended, until the start's
        # record is written (``take_startup_phases(close=True)``)
        self._phases: List[dict] = []
        self.startup_closed = False
        self.startup_taken = 0  # records written so far (an agent: many)

    def span(self, name: str, book: Optional[str] = None, **stats) -> _Span:
        """``with acc.span(name, **stats):`` — annotate under ``name`` and
        book under ``book`` (default ``name``; ``""`` books nothing, for a
        span that only frames its children on the trace)."""
        return _Span(self, name, name if book is None else book, stats)

    def startup_span(self, phase: str, **stats) -> _Span:
        """``with acc.startup_span("backend"):`` — the span
        ``startup.<phase>``, kept as a phase of this start. Once the
        start's record is written, or inside another phase on the same
        thread, it only annotates: a phase's code called again in steady
        state (a reload, a re-plan) is not start-up, and phases never
        nest, so their seconds add up to wall time."""
        name = "startup." + phase
        if self.startup_closed or getattr(self._local, "startup_open", False):
            return _Span(self, name, "", stats)
        return _StartupSpan(self, name, stats)

    def add_startup_phase(self, phase: str, unix_ns: int, dur_s: float) -> None:
        """A phase measured elsewhere: the time before this module could
        be imported (process start to the first line that ran)."""
        if not self.startup_closed:
            self.add("startup." + phase, dur_s)
            self._keep_phase("startup." + phase, unix_ns, dur_s)

    def _keep_phase(self, name: str, unix_ns: int, dur_s: float) -> None:
        with self._lock:
            self._phases.append(
                {"name": name, "unix_ns": unix_ns, "s": round(max(dur_s, 0.0), 6)}
            )

    def take_startup_phases(self, close: bool = False) -> List[dict]:
        """The phases kept so far, handed over (the list starts anew: an
        agent writes one record a worker start). ``close`` ends this
        process's start-up: later ``startup_span`` calls keep nothing."""
        with self._lock:
            phases, self._phases = self._phases, []
            self.startup_taken += 1
            if close:
                self.startup_closed = True
        return phases

    def add(self, name: str, dur_s: float) -> None:
        """Book one duration measured elsewhere."""
        self._book(name, dur_s, dur_s)

    def _book(self, name: str, dur_s: float, self_s: float) -> None:
        if dur_s < 0.0:  # clock skew must not go negative
            dur_s = 0.0
        if self_s < 0.0:
            self_s = 0.0
        bucket = _hist_bucket(dur_s)
        with self._lock:
            stat = self._stats.get(name)
            if stat is None:
                stat = self._stats[name] = SpanStat()
            stat.total_s += dur_s
            stat.self_s += self_s
            stat.count += 1
            if dur_s > stat.max_s:
                stat.max_s = dur_s
            stat.hist[bucket] += 1

    def count(self, name: str, n: float = 1) -> None:
        """Add ``n`` (a count, or seconds for a ``*_s`` name) to a counter."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def stats(self) -> Dict[str, SpanStat]:
        """Snapshot of the per-name stats (one C-level copy: readers on
        other threads never see the dict resize)."""
        return dict(self._stats)

    def totals(self) -> Dict[str, float]:
        """Name -> inclusive seconds booked so far: numbers, not the live
        stats, so that two calls can be subtracted."""
        return {name: stat.total_s for name, stat in self.stats().items()}

    def counters(self) -> Dict[str, float]:
        return dict(self._counters)

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
            self._counters.clear()
            self._phases.clear()
            self.startup_closed = False
            self.startup_taken = 0


_process = SpanAccumulator()


def process_accumulator() -> SpanAccumulator:
    """The process-local accumulator behind :func:`span`."""
    return _process


def span(name: str, **stats) -> _Span:
    """``with span("ckpt.save", step=7):`` on the process accumulator."""
    return _Span(_process, name, name, stats)


def startup_span(phase: str, **stats) -> _Span:
    """``with startup_span("backend"):`` on the process accumulator."""
    return _process.startup_span(phase, **stats)


def startup_phase(phase: str):
    """Decorator: the whole call is the start-up phase ``phase``."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with _process.startup_span(phase):
                return fn(*args, **kwargs)

        return inner

    return wrap
