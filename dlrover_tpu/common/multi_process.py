"""Local inter-process primitives: shared memory + socket-served lock/queue/dict.

Re-creates ``dlrover/python/common/multi_process.py:180-736`` for the TPU
agent↔trainer split: the agent (per-host supervisor) owns the server side of
each primitive over a unix domain socket; the JAX training process connects
as a client.  Checkpoint bytes go through POSIX shared memory; control goes
through these sockets.

Design difference from the reference: one generic request/response socket
protocol (msgpack frames) instead of pickled per-class request objects.
"""

import hashlib
import os
import socket
import uuid
import struct
import threading
import time
import queue as _queue
from multiprocessing import shared_memory
from typing import Any, Dict, List, Optional

import msgpack
from multiprocessing import resource_tracker

from .log import logger

SOCKET_TMP_DIR = os.getenv(
    "DLROVER_IPC_DIR", os.path.join("/tmp", "dlrover_tpu", "sockets")
)

_LEN = struct.Struct("!I")


def _ipc_namespace() -> str:
    """Machine-local IPC namespace. Normally the job name; when several
    simulated "hosts" of one job share a real machine (chaos/e2e tests,
    standalone multi-agent runs), DLROVER_IPC_NAMESPACE gives each its
    own namespace — matching production, where shm/sockets are per-host."""
    return os.getenv("DLROVER_IPC_NAMESPACE") or os.getenv(
        "DLROVER_JOB_NAME", "local"
    )


def _socket_path(name: str) -> str:
    os.makedirs(SOCKET_TMP_DIR, exist_ok=True)
    job = _ipc_namespace()
    fname = f"{job}_{name}.sock"
    path = os.path.join(SOCKET_TMP_DIR, fname)
    # AF_UNIX sun_path is limited to ~108 bytes; hash long names down.
    if len(path) > 100:
        digest = hashlib.sha1(fname.encode()).hexdigest()[:16]
        path = os.path.join(SOCKET_TMP_DIR, f"s_{digest}.sock")
    return path


def _send_frame(sock: socket.socket, payload: Dict[str, Any]) -> None:
    data = msgpack.packb(payload, use_bin_type=True)
    sock.sendall(_LEN.pack(len(data)) + data)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("socket closed")
        buf += chunk
    return buf


def _recv_frame(sock: socket.socket) -> Dict[str, Any]:
    (length,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    return msgpack.unpackb(_recv_exact(sock, length), raw=False, strict_map_key=False)


class LocalSocketServer:
    """Threaded unix-socket server dispatching {"m": method, "a": args}."""

    def __init__(self, name: str):
        self.name = name
        self.path = _socket_path(name)
        if os.path.exists(self.path):
            os.unlink(self.path)
        self._sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        self._sock.bind(self.path)
        self._sock.listen(64)
        self._stopped = False
        self._resp_cache: Dict[str, Dict[str, Any]] = {}
        self._cache_lock = threading.Lock()
        self._conn_local = threading.local()
        self._thread = threading.Thread(
            target=self._accept_loop, name=f"ipc-{name}", daemon=True
        )
        self._thread.start()

    def _accept_loop(self) -> None:
        while not self._stopped:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            threading.Thread(
                target=self._serve_conn, args=(conn,), daemon=True
            ).start()

    # Methods whose semantics are bound to the *connection* (e.g. lock
    # ownership) must re-execute on retransmit rather than replay a cached
    # response — a reconnect means the old connection's effects (like a
    # force-released lock) are gone.
    UNCACHED_METHODS: frozenset = frozenset()

    def _serve_conn(self, conn: socket.socket) -> None:
        conn_id = id(conn)
        # At-most-once execution: a cache entry is installed *before*
        # dispatch, so a retransmit arriving while the original is still
        # executing waits for that execution instead of running the op
        # twice (which would e.g. silently drop a queue item).
        try:
            with conn:
                while not self._stopped:
                    try:
                        req = _recv_frame(conn)
                    except (ConnectionError, OSError):
                        return
                    cid, seq = req.get("cid"), req.get("seq")
                    cacheable = (
                        cid is not None and req["m"] not in self.UNCACHED_METHODS
                    )
                    entry = None
                    if cacheable:
                        with self._cache_lock:
                            cached = self._resp_cache.get(cid)
                            if cached is not None and cached["seq"] == seq:
                                entry = cached
                            else:
                                entry = {
                                    "seq": seq,
                                    "done": threading.Event(),
                                    "resp": None,
                                    "mine": True,
                                }
                                self._resp_cache[cid] = entry
                                while len(self._resp_cache) > 4096:
                                    oldest = next(iter(self._resp_cache))
                                    if oldest == cid:
                                        break
                                    self._resp_cache.pop(oldest, None)
                        if not entry.get("mine"):
                            # Retransmit: wait for the original execution.
                            entry["done"].wait(timeout=300)
                            resp = entry["resp"] or {
                                "ok": False,
                                "err": "original request still in flight",
                            }
                            try:
                                _send_frame(conn, resp)
                                continue
                            except OSError:
                                return
                        entry["mine"] = False
                    try:
                        result = self._dispatch(
                            req["m"], req.get("a") or {}, conn_id
                        )
                        resp = {"ok": True, "r": result}
                    except Exception as e:  # noqa: BLE001 — reported to client
                        resp = {"ok": False, "err": repr(e)}
                    if entry is not None:
                        entry["resp"] = resp
                        entry["done"].set()
                    try:
                        _send_frame(conn, resp)
                    except OSError:
                        return
        finally:
            self._on_conn_closed(conn_id)

    def _on_conn_closed(self, conn_id: int) -> None:
        """Hook: subclasses release per-connection resources (e.g. locks)."""

    def _dispatch(self, method: str, args: Dict[str, Any], conn_id: int) -> Any:
        fn = getattr(self, "op_" + method, None)
        if fn is None:
            raise ValueError(f"unknown method {method}")
        self._conn_local.conn_id = conn_id
        return fn(**args)

    def stop(self) -> None:
        self._stopped = True
        try:
            self._sock.close()
        finally:
            if os.path.exists(self.path):
                try:
                    os.unlink(self.path)
                except OSError:
                    pass


class LocalSocketClient:
    """Client for :class:`LocalSocketServer`; reconnects lazily."""

    def __init__(self, name: str, timeout: float = 60.0):
        self.name = name
        self.path = _socket_path(name)
        self._timeout = timeout
        self._sock: Optional[socket.socket] = None
        self._lock = threading.Lock()
        self._cid = uuid.uuid4().hex
        self._seq = 0

    def _connect(self) -> socket.socket:
        deadline = time.time() + self._timeout
        while True:
            try:
                s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                s.connect(self.path)
                return s
            except (FileNotFoundError, ConnectionRefusedError):
                if time.time() > deadline:
                    raise TimeoutError(f"IPC server {self.name} unavailable")
                time.sleep(0.1)

    def call(self, method: str, **args: Any) -> Any:
        with self._lock:
            self._seq += 1
            req = {"m": method, "a": args, "cid": self._cid, "seq": self._seq}
            for attempt in (0, 1):
                if self._sock is None:
                    self._sock = self._connect()
                try:
                    _send_frame(self._sock, req)
                    resp = _recv_frame(self._sock)
                    break
                except (ConnectionError, OSError):
                    self._sock = None
                    if attempt == 1:
                        raise
        if not resp["ok"]:
            raise RuntimeError(f"IPC {self.name}.{method}: {resp['err']}")
        return resp["r"]

    def available(self) -> bool:
        """True only if a server is actually accepting on the socket.

        A bare path-exists check reports a socket file left behind by a
        SIGKILLed server as alive, which makes callers (e.g. the
        checkpoint engine's standalone auto-detection) neither start
        their own server nor reach one.
        """
        if not os.path.exists(self.path):
            return False
        try:
            s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            s.settimeout(2.0)
            s.connect(self.path)
            s.close()
            return True
        except OSError:
            return False

    def close(self) -> None:
        with self._lock:
            if self._sock is not None:
                self._sock.close()
                self._sock = None


# ---------------------------------------------------------------------------
# SharedLock
# ---------------------------------------------------------------------------


class SharedLockServer(LocalSocketServer):
    """Lock with reentrancy (hold count) and death-of-holder release.

    The holding client's connection id is recorded at acquire time; if that
    connection drops (client process crashed), the lock is force-released so
    waiters — typically the agent draining a checkpoint after a trainer
    crash — never deadlock.
    """

    UNCACHED_METHODS = frozenset({"acquire", "release", "locked"})

    def __init__(self, name: str):
        # Subclass state BEFORE super().__init__: the base constructor
        # starts the accept thread, and a client connecting (and
        # dropping — which runs _on_conn_closed) in that window must
        # find _cond et al. already present, or the handler thread dies
        # and the server silently mis-tracks the disconnect.
        self._locked_by: Optional[str] = None
        self._holder_conn: Optional[int] = None
        self._hold_count = 0
        self._cond = threading.Condition()
        super().__init__("lock_" + name)

    def op_acquire(self, owner: str, blocking: bool = True, timeout: float = -1.0) -> bool:
        conn_id = self._conn_local.conn_id
        deadline = None if timeout < 0 else time.time() + timeout
        with self._cond:
            while self._locked_by is not None and self._locked_by != owner:
                if not blocking:
                    return False
                wait = None if deadline is None else max(0.0, deadline - time.time())
                if wait == 0.0 or not self._cond.wait(timeout=wait or 1.0):
                    if deadline is not None and time.time() >= deadline:
                        return False
            self._locked_by = owner
            self._holder_conn = conn_id
            self._hold_count += 1
            return True

    def op_release(self, owner: str) -> bool:
        with self._cond:
            if self._locked_by == owner:
                self._hold_count -= 1
                if self._hold_count <= 0:
                    self._locked_by = None
                    self._holder_conn = None
                    self._hold_count = 0
                    self._cond.notify_all()
                return True
            return False

    def op_locked(self) -> bool:
        with self._cond:
            return self._locked_by is not None

    def _on_conn_closed(self, conn_id: int) -> None:
        with self._cond:
            if self._holder_conn == conn_id and self._locked_by is not None:
                logger.warning(
                    "lock %s force-released: holder %s connection dropped",
                    self.name,
                    self._locked_by,
                )
                self._locked_by = None
                self._holder_conn = None
                self._hold_count = 0
                self._cond.notify_all()


class SharedLock:
    """Cross-process lock; ``name`` scopes it within the job."""

    def __init__(self, name: str, create: bool = False):
        self.name = name
        self._server = SharedLockServer(name) if create else None
        self._client = LocalSocketClient("lock_" + name)
        self._owner = f"{os.getpid()}_{id(self)}"

    def acquire(self, blocking: bool = True, timeout: float = -1.0) -> bool:
        return self._client.call(
            "acquire", owner=self._owner, blocking=blocking, timeout=timeout
        )

    def release(self) -> bool:
        return self._client.call("release", owner=self._owner)

    def locked(self) -> bool:
        return self._client.call("locked")

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()

    def close(self) -> None:
        self._client.close()
        if self._server:
            self._server.stop()


# ---------------------------------------------------------------------------
# SharedQueue
# ---------------------------------------------------------------------------


class SharedQueueServer(LocalSocketServer):
    def __init__(self, name: str, maxsize: int = 0):
        # state before super(): see SharedLockServer.__init__
        self._queue: "_queue.Queue[Any]" = _queue.Queue(maxsize)
        super().__init__("queue_" + name)

    def op_put(self, item: Any, block: bool = True, timeout: Optional[float] = None) -> bool:
        try:
            self._queue.put(item, block=block, timeout=timeout)
            return True
        except _queue.Full:
            return False

    def op_get(self, block: bool = True, timeout: Optional[float] = None) -> Any:
        try:
            return {"found": True, "item": self._queue.get(block=block, timeout=timeout)}
        except _queue.Empty:
            return {"found": False, "item": None}

    def op_qsize(self) -> int:
        return self._queue.qsize()

    def op_empty(self) -> bool:
        return self._queue.empty()


class SharedQueue:
    def __init__(self, name: str, create: bool = False, maxsize: int = 0):
        self.name = name
        self._server = SharedQueueServer(name, maxsize) if create else None
        self._client = LocalSocketClient("queue_" + name)

    def put(self, item: Any, block: bool = True, timeout: Optional[float] = None) -> bool:
        return self._client.call("put", item=item, block=block, timeout=timeout)

    def get(self, block: bool = True, timeout: Optional[float] = None) -> Any:
        # Poll with short server-side timeouts so one slow get does not pin
        # the connection; semantics match queue.Queue.get.
        deadline = None if timeout is None else time.time() + timeout
        while True:
            chunk = 1.0
            if deadline is not None:
                remaining = deadline - time.time()
                if remaining <= 0 and block:
                    raise _queue.Empty
                chunk = min(chunk, max(0.0, remaining))
            resp = self._client.call(
                "get", block=block, timeout=chunk if block else None
            )
            if resp["found"]:
                return resp["item"]
            if not block:
                raise _queue.Empty
            if deadline is not None and time.time() >= deadline:
                raise _queue.Empty
            # Yield between polls: the client lock is released and taken
            # again within a few bytecodes, and threading.Lock is not
            # fair — a put() from another thread of this process (the
            # saver's shutdown handing the runner its exit message)
            # otherwise starves behind a blocked get() for minutes.
            time.sleep(0.001)

    def qsize(self) -> int:
        return self._client.call("qsize")

    def empty(self) -> bool:
        return self._client.call("empty")

    def available(self) -> bool:
        """True while a server is accepting on this queue's socket —
        i.e. the owning process is alive (liveness probe for callers
        blocked on work the server should be doing)."""
        return self._client.available()

    def close(self) -> None:
        self._client.close()
        if self._server:
            self._server.stop()


# ---------------------------------------------------------------------------
# SharedDict
# ---------------------------------------------------------------------------


class SharedDictServer(LocalSocketServer):
    def __init__(self, name: str):
        # state before super(): see SharedLockServer.__init__
        self._dict: Dict[Any, Any] = {}
        self._lock = threading.Lock()
        super().__init__("dict_" + name)

    def op_set(self, key: Any, value: Any) -> None:
        with self._lock:
            self._dict[key] = value

    def op_update(self, mapping: Dict[Any, Any]) -> None:
        with self._lock:
            self._dict.update(mapping)

    def op_get(self, key: Any, default: Any = None) -> Any:
        with self._lock:
            return self._dict.get(key, default)

    def op_get_all(self) -> Dict[Any, Any]:
        with self._lock:
            return dict(self._dict)

    def op_delete(self, key: Any) -> None:
        with self._lock:
            self._dict.pop(key, None)

    def op_clear(self) -> None:
        with self._lock:
            self._dict.clear()


class SharedDict:
    def __init__(self, name: str, create: bool = False):
        self.name = name
        self._server = SharedDictServer(name) if create else None
        self._client = LocalSocketClient("dict_" + name)

    def set(self, key: Any, value: Any) -> None:
        self._client.call("set", key=key, value=value)

    def update(self, mapping: Dict[Any, Any]) -> None:
        self._client.call("update", mapping=mapping)

    def get(self, key: Any, default: Any = None) -> Any:
        return self._client.call("get", key=key, default=default)

    def get_all(self) -> Dict[Any, Any]:
        return self._client.call("get_all")

    def delete(self, key: Any) -> None:
        self._client.call("delete", key=key)

    def clear(self) -> None:
        self._client.call("clear")

    def close(self) -> None:
        self._client.close()
        if self._server:
            self._server.stop()


# ---------------------------------------------------------------------------
# Shared memory
# ---------------------------------------------------------------------------


def _shm_name(name: str) -> str:
    return f"dlrover_{_ipc_namespace()}_{name}"


# Mappings whose close() hit "BufferError: cannot close exported pointers
# exist" — something (a numpy view, a CPU-backend jax.Array aliasing host
# memory) still references the mmap. Quarantined with a strong reference so
# SharedMemory.__del__ never runs on them (an unraisable BufferError in a
# finalizer is uncatchable by callers); retried opportunistically once the
# exporting views die. Guarded: concurrent close() calls (persister thread
# vs trainer) must not lose a quarantined entry in the sweep's rewrite.
_UNCLOSEABLE: List[shared_memory.SharedMemory] = []
_UNCLOSEABLE_LOCK = threading.Lock()


def _sweep_uncloseable() -> None:
    with _UNCLOSEABLE_LOCK:
        still = []
        for shm in _UNCLOSEABLE:
            try:
                shm.close()
            except BufferError:
                still.append(shm)
            except Exception as e:  # noqa: BLE001 — sweep, best effort
                logger.debug("deferred shm close: %r", e)
        _UNCLOSEABLE[:] = still


class SharedMemorySegment:
    """POSIX shared-memory segment with create-or-attach-and-resize semantics.

    Reference: ``SharedMemoryHandler`` (``ckpt_saver.py:234-397``) —
    checkpoint bytes are staged here by the trainer and drained by the agent.
    """

    def __init__(self, name: str):
        self.name = _shm_name(name)
        self._shm: Optional[shared_memory.SharedMemory] = None
        self._ino: Optional[int] = None

    @staticmethod
    def _untrack(shm: shared_memory.SharedMemory) -> None:
        # CPython's resource tracker unlinks "leaked" segments when the
        # creating process exits — which would destroy a staged checkpoint
        # exactly when the trainer crashes. Lifetime is managed explicitly
        # by the agent through unlink(), so always untrack.
        try:
            resource_tracker.unregister(shm._name, "shared_memory")  # noqa: SLF001
        except Exception as e:  # noqa: BLE001 — tracker impl varies by platform
            logger.debug("resource tracker unregister: %r", e)

    @staticmethod
    def _posix_unlink(shm: shared_memory.SharedMemory) -> None:
        # Unlink via the posix call directly: SharedMemory.unlink() would
        # also unregister from the resource tracker, which _untrack already
        # did (double-unregister prints KeyErrors from the tracker daemon).
        try:
            shared_memory._posixshmem.shm_unlink(shm._name)  # noqa: SLF001
        except FileNotFoundError:
            pass

    def _path(self) -> str:
        return os.path.join("/dev/shm", self.name)

    def _file_ino(self) -> Optional[int]:
        try:
            return os.stat(self._path()).st_ino
        except OSError:
            return None

    def _record_ino(self) -> None:
        # Prefer the mapped fd's inode (no race with concurrent recreate).
        fd = getattr(self._shm, "_fd", -1)
        try:
            self._ino = os.fstat(fd).st_ino if fd >= 0 else self._file_ino()
        except OSError:
            self._ino = self._file_ino()

    @property
    def size(self) -> int:
        return self._shm.size if self._shm else 0

    @property
    def buf(self):
        return self._shm.buf if self._shm else None

    def exists(self) -> bool:
        return os.path.exists(self._path())

    def ensure(self, size: int) -> None:
        """Create the segment, growing (recreating) it if too small."""
        if self._shm is not None and self._shm.size >= size:
            return
        if self._shm is not None:
            self.unlink()
        try:
            self._shm = self._create_reserved(size)
        except FileExistsError:
            existing = shared_memory.SharedMemory(name=self.name)
            self._untrack(existing)
            if existing.size >= size:
                self._shm = existing
            else:
                existing.close()
                self._posix_unlink(existing)
                self._shm = self._create_reserved(size)
        self._untrack(self._shm)
        self._record_ino()

    def _create_reserved(self, size: int) -> shared_memory.SharedMemory:
        """Create the segment with its pages RESERVED. ``ftruncate`` on
        tmpfs reserves nothing, so a segment larger than what /dev/shm
        has left would be created fine and then kill the writer with
        SIGBUS mid-copy; ``posix_fallocate`` makes the shortage an
        ``OSError`` (ENOSPC) here, where the caller can see it."""
        shm = shared_memory.SharedMemory(name=self.name, create=True, size=size)
        try:
            os.posix_fallocate(shm._fd, 0, size)
        except OSError:
            self._untrack(shm)
            shm.close()
            self._posix_unlink(shm)
            raise
        return shm

    def attach(self) -> bool:
        if self._shm is not None:
            # The creator may have grown the segment (unlink + recreate
            # under the same name); a cached mapping would then silently
            # read the orphaned old segment. Detect via inode change.
            if self._file_ino() == self._ino and self._ino is not None:
                return True
            self.close()
        try:
            self._shm = shared_memory.SharedMemory(name=self.name)
            self._untrack(self._shm)
            self._record_ino()
            return True
        except FileNotFoundError:
            return False

    def write(self, data: bytes, offset: int = 0) -> None:
        assert self._shm is not None
        self._shm.buf[offset : offset + len(data)] = data

    def read(self, offset: int, length: int) -> bytes:
        assert self._shm is not None
        return bytes(self._shm.buf[offset : offset + length])

    @staticmethod
    def _close_or_quarantine(shm: shared_memory.SharedMemory) -> None:
        """Close a mapping; never raise. A mapping with live exported
        views goes to the quarantine list (strong ref) so its __del__
        can't fire an unraisable BufferError at GC time."""
        _sweep_uncloseable()
        try:
            shm.close()
        except BufferError:
            with _UNCLOSEABLE_LOCK:
                _UNCLOSEABLE.append(shm)
        except Exception as e:  # noqa: BLE001 — teardown
            logger.debug("shm close: %r", e)

    def close(self) -> None:
        if self._shm is not None:
            shm, self._shm = self._shm, None
            self._close_or_quarantine(shm)

    def unlink(self) -> None:
        if self._shm is None and not self.attach():
            return
        shm, self._shm = self._shm, None
        self._ino = None
        self._close_or_quarantine(shm)
        self._posix_unlink(shm)
