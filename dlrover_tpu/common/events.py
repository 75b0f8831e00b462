"""Structured training-event SDK.

Re-creates the reference's ``dlrover/python/training_event`` package
(EventEmitter/DurationSpan ``emitter.py:37,136``, AsyncExporter +
Text/Console exporters ``exporter.py:51,183,229``): crash-safe, append-only
instant and span events used for goodput accounting, hang detection input,
and post-mortem timelines.
"""

import atexit
import json
import os
import queue
import sys
import threading
import time
import uuid
from typing import Any, Dict, Optional

from ..observability import flight_recorder, trace
from ..observability.spans import annotation
from .log import logger


class EventType:
    INSTANT = "instant"
    BEGIN = "begin"
    END = "end"


class Event:
    __slots__ = (
        "event_id",
        "event_time",
        "target",
        "name",
        "event_type",
        "content",
        "pid",
        "trace_id",
        "span_id",
    )

    def __init__(self, target: str, name: str, event_type: str, content: Dict[str, Any]):
        self.event_id = uuid.uuid4().hex[:16]
        self.event_time = time.time()
        self.target = target
        self.name = name
        self.event_type = event_type
        self.content = content
        self.pid = os.getpid()
        # Incident correlation: empty outside an active trace, so
        # steady-state event lines keep their pre-trace shape.
        self.trace_id, self.span_id = trace.current_ids()

    def to_dict(self) -> Dict[str, Any]:
        d = {
            "id": self.event_id,
            "ts": round(self.event_time, 6),
            "pid": self.pid,
            "target": self.target,
            "name": self.name,
            "type": self.event_type,
            "content": self.content,
        }
        if self.trace_id:
            d["trace_id"] = self.trace_id
            d["span_id"] = self.span_id
        return d

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), default=str)


class Exporter:
    def export(self, event: Event) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class ConsoleExporter(Exporter):
    def export(self, event: Event) -> None:
        print(event.to_json(), file=sys.stderr)


class TextFileExporter(Exporter):
    def __init__(self, dir_path: str, prefix: str = "events"):
        os.makedirs(dir_path, exist_ok=True)
        name = f"{prefix}_{os.getpid()}_{int(time.time())}.jsonl"
        self._path = os.path.join(dir_path, name)
        self._file = open(self._path, "a", buffering=1)

    def export(self, event: Event) -> None:
        self._file.write(event.to_json() + "\n")

    def close(self) -> None:
        try:
            self._file.close()
        except Exception as e:  # noqa: BLE001 — teardown
            logger.debug("event file close: %r", e)


class AsyncExporter(Exporter):
    """Queue + daemon-thread wrapper so emission never blocks training."""

    def __init__(self, inner: Exporter, max_queue: int = 10000):
        self._inner = inner
        self._queue: "queue.Queue[Optional[Event]]" = queue.Queue(max_queue)
        self._dropped = 0
        self._drop_counter = None  # registry counter, bound on first drop
        self._thread = threading.Thread(
            target=self._run, name="event-exporter", daemon=True
        )
        self._thread.start()
        atexit.register(self.close)

    @property
    def dropped(self) -> int:
        """Events lost to a full queue or a failing sink."""
        return self._dropped

    def _count_drop(self) -> None:
        self._dropped += 1
        try:
            if self._drop_counter is None:
                from ..observability.metrics import get_registry

                self._drop_counter = get_registry().counter(
                    "dlrover_events_dropped_total"
                )
            self._drop_counter.inc()
        # tpulint: ignore[exception-swallow] the drop is already journaled in _dropped above; the registry mirror is best-effort and must not break the drop path
        except Exception:  # noqa: BLE001 — metrics must not break the drop path
            pass

    def export(self, event: Event) -> None:
        try:
            self._queue.put_nowait(event)
        except queue.Full:
            self._count_drop()

    def _run(self) -> None:
        while True:
            event = self._queue.get()
            if event is None:
                break
            try:
                self._inner.export(event)
            except Exception as e:  # noqa: BLE001 — exporter must outlive sinks
                self._count_drop()
                logger.debug("event export failed: %r", e)

    def close(self) -> None:
        try:
            # Block (bounded) so a full queue still gets its sentinel and the
            # worker drains end-of-job events before the inner exporter closes.
            self._queue.put(None, timeout=5)
        except queue.Full:
            pass
        self._thread.join(timeout=10)
        if self._dropped:
            # Post-drain summary straight to the sink: the one durable
            # breadcrumb that the timeline has holes (and how many).
            # Written synchronously so a full queue can't drop the
            # drop report itself; its own failure is not re-counted.
            try:
                self._inner.export(
                    Event(
                        "events",
                        "events_dropped",
                        EventType.INSTANT,
                        {"dropped": self._dropped},
                    )
                )
            except Exception as e:  # noqa: BLE001 — teardown best-effort
                logger.debug("drop-summary export failed: %r", e)
        self._inner.close()


class DurationSpan:
    """Context manager emitting paired begin/end events. Used as a
    ``with`` block (one thread), it also opens a profiler annotation of
    its own name, so the incident stream's intervals show on a
    ``jax.profiler`` trace under the names ``tpurun-trace`` prints; a
    ``begin()``/``end()`` pair may cross threads and opens none."""

    def __init__(self, emitter: "EventEmitter", name: str, content: Dict[str, Any]):
        self._emitter = emitter
        self.name = name
        self.content = dict(content)
        self._begin_time: Optional[float] = None
        self._ended = False
        self._trace_token = None
        self._span_ctx = None
        self._annotation = None

    def begin(self) -> "DurationSpan":
        self._begin_time = time.time()
        # Child span for the duration: begin/end share a span_id and
        # events emitted inside nest under it in the merged trace.
        self._trace_token = trace.push_child()
        # Remember the child context so end() can re-enter it even on
        # a different thread (revoke issued on the scheduler's eval
        # thread, release confirmed on the tenant's drain thread).
        self._span_ctx = trace.current() if self._trace_token else None
        self._emitter.emit(self.name, EventType.BEGIN, self.content)
        return self

    def end(self, extra: Optional[Dict[str, Any]] = None) -> None:
        if self._ended:
            return
        self._ended = True
        content = dict(self.content)
        if extra:
            content.update(extra)
        if self._begin_time is not None:
            content["duration_s"] = round(time.time() - self._begin_time, 6)
        reenter = None
        if self._span_ctx is not None and trace.current() is not self._span_ctx:
            reenter = trace.enter(self._span_ctx)
        self._emitter.emit(self.name, EventType.END, content)
        trace.release(reenter)
        trace.release(self._trace_token)
        self._trace_token = None
        self._span_ctx = None

    def fail(self, error: str) -> None:
        self.end({"error": error, "success": False})

    def __enter__(self) -> "DurationSpan":
        self._annotation = annotation(self.name)
        if self._annotation is not None:
            self._annotation.__enter__()
        return self.begin()

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self.fail(repr(exc))
        else:
            self.end()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
            self._annotation = None


class EventEmitter:
    def __init__(self, target: str, exporter: Optional[Exporter] = None):
        self.target = target
        self._exporter = exporter or _default_exporter()

    def emit(self, name: str, event_type: str, content: Dict[str, Any]) -> None:
        try:
            event = Event(self.target, name, event_type, content)
            # Ring first: the flight recorder must see the event even
            # when the exporter path is the thing that is failing.
            flight_recorder.record_event(event.to_dict())
            self._exporter.export(event)
        except Exception:
            logger.debug("failed to emit event %s", name, exc_info=True)

    def instant(self, name: str, **content: Any) -> None:
        self.emit(name, EventType.INSTANT, content)

    def duration(self, name: str, **content: Any) -> DurationSpan:
        return DurationSpan(self, name, content)


_default: Optional[Exporter] = None
_default_lock = threading.Lock()


def _default_exporter() -> Exporter:
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                event_dir = os.getenv("DLROVER_EVENT_DIR", "")
                if event_dir:
                    _default = AsyncExporter(TextFileExporter(event_dir))
                else:
                    _default = _NullExporter()
    return _default


class _NullExporter(Exporter):
    def export(self, event: Event) -> None:
        pass


_global_emitter: Optional[EventEmitter] = None


def global_emitter() -> EventEmitter:
    """Process-scoped emitter for cross-cutting events (crash reports,
    fatal signals) that belong to no specific subsystem."""
    global _global_emitter
    if _global_emitter is None:
        _global_emitter = EventEmitter("process")
    return _global_emitter


def flush_default_exporter() -> None:
    """Drain + close the shared async exporter NOW (crash path: the
    ErrorHandler calls this before the interpreter dies; a fresh
    exporter is rebuilt lazily if anything emits afterwards)."""
    global _default
    with _default_lock:
        exporter, _default = _default, None
    if exporter is not None:
        try:
            exporter.close()
        except Exception:  # noqa: BLE001 — crash path
            logger.debug("default exporter close failed", exc_info=True)


# Predefined emitters (reference: training_event/predefined/)
class AgentEvents:
    def __init__(self):
        self._em = EventEmitter("agent")

    def start(self, **kw):
        self._em.instant("agent_start", **kw)

    def rendezvous(self, rdzv_name: str, round: int, **kw) -> DurationSpan:
        return self._em.duration("rendezvous", rdzv_name=rdzv_name, round=round, **kw)

    def process_restart(self, **kw):
        self._em.instant("process_restart", **kw)

    def process_fail(self, **kw):
        self._em.instant("process_fail", **kw)

    def exit(self, reason: str = ""):
        self._em.instant("agent_exit", reason=reason)


class MasterEvents:
    def __init__(self):
        self._em = EventEmitter("master")

    def start(self, **kw):
        self._em.instant("master_start", **kw)

    def node_join(self, node_id: int, **kw):
        self._em.instant("node_join", node_id=node_id, **kw)

    def node_relaunch(self, node_id: int, **kw):
        self._em.instant("node_relaunch", node_id=node_id, **kw)

    def rendezvous_complete(self, rdzv_name: str, round: int, world_size: int):
        self._em.instant(
            "rendezvous_complete",
            rdzv_name=rdzv_name,
            round=round,
            world_size=world_size,
        )

    def job_stop(self, reason: str = ""):
        self._em.instant("job_stop", reason=reason)


class TrainerEvents:
    def __init__(self):
        self._em = EventEmitter("trainer")

    def step(self, step: int, **kw):
        self._em.instant("train_step", step=step, **kw)

    def ckpt_save(self, step: int, storage: str) -> DurationSpan:
        return self._em.duration("ckpt_save", step=step, storage=storage)

    def ckpt_load(self, **kw) -> DurationSpan:
        return self._em.duration("ckpt_load", **kw)
