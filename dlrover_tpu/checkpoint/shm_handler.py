"""Shared-memory staging of jax pytrees (the "flash" in flash checkpoint).

Reference mechanism: ``SharedMemoryHandler`` (``ckpt_saver.py:234-397``) —
trainer memcpys tensors into POSIX shm; the agent persists asynchronously.
TPU version: the unit staged is each *addressable unique* device shard
(replica_id 0) of each pytree leaf, after an async device→host copy, so
the trainer blocks only for the D2H + memcpy, never for storage IO.

Layout of the segment: [u64 meta_len][meta JSON][payload bytes...].
"""

import os
import time
from concurrent.futures import Future, ThreadPoolExecutor, wait
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..common.log import logger
from ..common.multi_process import SharedMemorySegment
from ..observability.spans import span
from .meta import (
    HEADER_LEN_BYTES,
    CheckpointMeta,
    ShardRecord,
    assemble_global,
    jsonable_to_spec,
    spec_to_jsonable,
)


_IMAGE_CHUNK = 8 << 20

# How ``save_pytree`` moves the payload (PERF.md section 5 has the tables
# these were chosen from; scripts/shm_copy_scaling.py makes the first):
# the payload goes in pieces of about _PIECE_BYTES whatever the leaves'
# sizes (a 154 MB embedding is ten pieces, fifty biases are one), and an
# image of _POOLED_MIN_BYTES or more is copied by up to _COPY_THREADS_MAX
# threads. On the v5e's host 8 threads reach 42 GB/s against one thread's
# 12 and 16 add nothing; 4 to 32 MiB a piece read alike, 2 MiB lower.
_PIECE_BYTES = 16 << 20
_COPY_THREADS_MAX = 8
_POOLED_MIN_BYTES = 32 << 20


def _copy_chunk(buf, offset: int, src: np.ndarray) -> None:
    """The copy primitive: ``src`` to ``offset`` of the segment, in C
    order. numpy drops the interpreter lock for the assignment, so
    chunks on several threads copy at once, and it converts the layout
    of a source that is not C-contiguous on the way (on a TPU host the
    ``wqkv`` leaves arrive with their first axis minor): no contiguous
    temporary is made of it first."""
    dst = np.ndarray(src.shape, dtype=src.dtype, buffer=buf, offset=offset)
    dst[...] = src


def _copy_piece(buf, piece: List[Tuple[int, np.ndarray]]) -> None:
    with span("ckpt.save.copy", bytes=sum(src.nbytes for _, src in piece)):
        for offset, src in piece:
            _copy_chunk(buf, offset, src)


class _PayloadCopy:
    """One save's copy of its payload into ``buf``, piece by piece: on
    ``pool``'s threads, or on the calling thread where ``pool`` is None."""

    def __init__(self, buf, pool: Optional[ThreadPoolExecutor]):
        self._buf = buf
        self._pool = pool
        self._pending: List[Tuple[int, np.ndarray]] = []
        self._pending_bytes = 0
        self._futures: List[Future] = []
        self.pieces = 0

    def add(self, offset: int, host: np.ndarray) -> None:
        """Queue ``host`` for ``offset`` of the segment; every piece
        that fills goes off at once. A C-contiguous array is cut at any
        byte, another one between its rows."""
        src = host.reshape(-1).view(np.uint8) if host.flags.c_contiguous else host
        if not src.nbytes:
            return
        row_bytes = src.nbytes // len(src)
        lo = 0
        while lo < len(src):
            room = _PIECE_BYTES - self._pending_bytes
            chunk = src[lo : lo + max(1, room // row_bytes)]
            self._pending.append((offset + lo * row_bytes, chunk))
            self._pending_bytes += chunk.nbytes
            lo += len(chunk)
            if self._pending_bytes >= _PIECE_BYTES:
                self._flush()

    def _flush(self) -> None:
        if not self._pending:
            return
        piece, self._pending, self._pending_bytes = self._pending, [], 0
        self.pieces += 1
        if self._pool is None:
            _copy_piece(self._buf, piece)
        else:
            self._futures.append(
                self._pool.submit(_copy_piece, self._buf, piece)
            )

    def finish(self) -> None:
        """Send the last piece and wait for every piece; raises what the
        first failed piece raised."""
        self._flush()
        for f in self._futures:
            f.result()

    def settle(self) -> None:
        """Leave no piece running or waiting to run, whatever happened
        (nothing to do after a ``finish`` that returned)."""
        for f in self._futures:
            f.cancel()
        wait(self._futures)


def segment_image_size(segment: SharedMemorySegment) -> int:
    """Logical byte length of a segment image
    (``[8B meta_len][meta JSON][payload]``), 0 when absent/invalid."""
    if not segment.attach():
        return 0
    try:
        meta_len = int.from_bytes(segment.read(0, HEADER_LEN_BYTES), "little")
        if meta_len <= 0 or meta_len > segment.size:
            return 0
        meta = CheckpointMeta.from_json(
            segment.read(HEADER_LEN_BYTES, meta_len).decode()
        )
        return HEADER_LEN_BYTES + meta_len + meta.total_bytes
    except Exception as e:  # noqa: BLE001 — torn/absent header reads as empty
        logger.debug("shm size probe: %r", e)
        return 0


def stream_into_segment(
    segment: SharedMemorySegment, total: int, read
) -> None:
    """Overwrite ``segment`` with a ``total``-byte image from ``read(n)``.

    Torn-write safe: the 8-byte header is zeroed first and written LAST,
    so a stream that dies mid-transfer leaves a segment whose meta never
    parses (readers see "empty") instead of a valid-looking image over a
    truncated payload. Raises on truncation; the header stays invalid.
    """
    segment.ensure(total)
    buf = segment.buf
    buf[:HEADER_LEN_BYTES] = b"\x00" * HEADER_LEN_BYTES
    header = b""
    off = 0
    while off < total:
        chunk = read(min(_IMAGE_CHUNK, total - off))
        if not chunk:
            raise IOError(f"segment image truncated at {off}/{total}")
        if off < HEADER_LEN_BYTES:
            take = min(len(chunk), HEADER_LEN_BYTES - off)
            header += chunk[:take]
            if len(chunk) > take:
                buf[off + take : off + len(chunk)] = chunk[take:]
        else:
            buf[off : off + len(chunk)] = chunk
        off += len(chunk)
    buf[:HEADER_LEN_BYTES] = header


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _leaf_records(path: str, leaf) -> List[Tuple[ShardRecord, Any]]:
    """Plan the shard records for one leaf (no data copied yet)."""
    records = []
    if isinstance(leaf, jax.Array) and hasattr(leaf, "addressable_shards"):
        spec = []
        try:
            spec = spec_to_jsonable(leaf.sharding.spec)
        except Exception as e:  # noqa: BLE001 — exotic sharding: no spec
            logger.debug("sharding spec not jsonable: %r", e)
            spec = []
        seen_indices = set()
        for shard in leaf.addressable_shards:
            # Dedupe by index among THIS HOST's shards only (NOT by
            # replica_id): on a multi-process mesh a replicated leaf's
            # replica_id-0 copy lives on ONE host — filtering on it
            # would leave every other host's shm empty for that leaf,
            # making its staged checkpoint unrestorable after a re-mesh.
            key = tuple(
                (s.start or 0, s.stop if s.stop is not None else dim)
                for s, dim in zip(shard.index, leaf.shape)
            )
            if key in seen_indices:
                continue
            seen_indices.add(key)
            local_shape = [b - a for a, b in key]
            rec = ShardRecord(
                path=path,
                global_shape=list(leaf.shape),
                local_shape=local_shape,
                dtype=str(leaf.dtype),
                index=list(key),
                offset=0,
                nbytes=int(np.dtype(leaf.dtype).itemsize * np.prod(local_shape or [1])),
                spec=spec,
            )
            records.append((rec, shard))
        return records
    # Host array / scalar: one full record
    arr = np.asarray(leaf)
    rec = ShardRecord(
        path=path,
        global_shape=list(arr.shape),
        local_shape=list(arr.shape),
        dtype=str(arr.dtype),
        index=[(0, d) for d in arr.shape],
        offset=0,
        nbytes=int(arr.nbytes),
        spec=[],
    )
    return [(rec, arr)]


class SharedMemoryHandler:
    """One shm segment per host shard of the checkpoint."""

    def __init__(self, host_rank: int = 0, name: str = ""):
        self.host_rank = host_rank
        self._segment = SharedMemorySegment(name or f"ckpt_shard_{host_rank}")
        self._pool: Optional[ThreadPoolExecutor] = None
        # on how many threads the last ``save_pytree`` copied its payload
        self.copy_threads = 1

    # -- trainer side ------------------------------------------------------

    def save_pytree(
        self,
        step: int,
        pytree: Any,
        num_hosts: int = 1,
        mesh=None,
        extra: Optional[Dict[str, Any]] = None,
    ) -> CheckpointMeta:
        """Stage ``pytree`` into the segment; returns once the whole
        image is there.

        The header is zeroed first and written LAST, after every byte of
        the payload: a trainer killed mid-stage, or a copy that raises,
        leaves an image that parses as absent, not a fresh meta over a
        torn payload (the agent's breakpoint save would persist it).
        The payload moves in pieces of ``_PIECE_BYTES``; an image of
        ``_POOLED_MIN_BYTES`` or more is copied by this handler's pool of
        ``min(CPUs of the process, _COPY_THREADS_MAX)`` threads while the
        calling thread waits for the next leaf's host copy, a smaller
        one (or a process with one CPU) on the calling thread, through
        the same code.

        Names its own time on the profiler's clock, under whichever root
        the caller opened (``ckpt.save``, or ``ckpt.stage`` on the
        staging thread). On the calling thread, never overlapping:
        ``ckpt.save.plan``, ``ckpt.save.ensure``, per leaf
        ``ckpt.save.d2h`` (the wait on the device-to-host copy) and
        ``ckpt.save.memcpy`` (handing the leaf to the copy, and copying
        where that is inline), and one last ``ckpt.save.memcpy`` (stats
        ``threads``, ``pieces``) that waits for the pieces: the
        ``memcpy`` spans sum to the wall time the caller gave to the
        copy. Each piece is a ``ckpt.save.copy`` (stat ``bytes``) on the
        thread that copied it."""
        with span("ckpt.save.plan"):
            meta, plan, meta_bytes = self._plan(
                step, pytree, num_hosts, mesh, extra
            )
        total = HEADER_LEN_BYTES + len(meta_bytes) + meta.total_bytes
        with span("ckpt.save.ensure", bytes=total):
            self._segment.ensure(total)
        buf = self._segment.buf
        buf[:HEADER_LEN_BYTES] = b"\x00" * HEADER_LEN_BYTES
        payload_base = HEADER_LEN_BYTES + len(meta_bytes)
        buf[HEADER_LEN_BYTES:payload_base] = meta_bytes
        pool = self._copy_pool(meta.total_bytes)
        copy = _PayloadCopy(buf, pool)
        try:
            for rec, shard in plan:
                if isinstance(shard, np.ndarray):
                    data = shard
                else:
                    data = getattr(shard, "data", shard)
                with span("ckpt.save.d2h"):
                    host = np.asarray(data)
                with span("ckpt.save.memcpy"):
                    if host.nbytes != rec.nbytes:
                        raise ValueError(
                            f"{rec.path}: {host.nbytes} bytes on the host, "
                            f"{rec.nbytes} planned"
                        )
                    copy.add(payload_base + rec.offset, host)
            with span(
                "ckpt.save.memcpy", threads=self.copy_threads
            ) as join:
                copy.finish()
                join.set(pieces=copy.pieces)
        finally:
            copy.settle()
        buf[:HEADER_LEN_BYTES] = len(meta_bytes).to_bytes(
            HEADER_LEN_BYTES, "little"
        )
        return meta

    def _copy_pool(self, nbytes: int) -> Optional[ThreadPoolExecutor]:
        """The pool a payload of ``nbytes`` is copied on, made on first
        use, or None where the calling thread copies; ``copy_threads``
        says which it was."""
        width = min(len(os.sched_getaffinity(0)), _COPY_THREADS_MAX)
        if nbytes < _POOLED_MIN_BYTES or width < 2:
            self.copy_threads = 1
            return None
        self.copy_threads = width
        if self._pool is None:
            self._pool = ThreadPoolExecutor(
                max_workers=width, thread_name_prefix="ckpt-copy"
            )
        return self._pool

    def _close_pool(self) -> None:
        pool, self._pool = self._pool, None
        if pool is not None:
            pool.shutdown(wait=True)

    def _plan(self, step, pytree, num_hosts, mesh, extra):
        """Flatten, plan one record per unique addressable shard, kick
        every device-to-host copy, lay the records out and encode the
        meta: everything before the first byte moves."""
        flat, _ = jax.tree_util.tree_flatten_with_path(pytree)
        plan: List[Tuple[ShardRecord, Any]] = []
        for path, leaf in flat:
            plan.extend(_leaf_records(_path_str(path), leaf))

        # Start all D2H copies before any blocking read (overlap on TPU).
        for _, shard in plan:
            if isinstance(shard, np.ndarray):
                # ndarray.data raises ValueError for non-buffer dtypes
                # (ml_dtypes bfloat16), and a host array has no D2H copy
                # to start anyway.
                continue
            data = getattr(shard, "data", None)
            if data is not None and hasattr(data, "copy_to_host_async"):
                data.copy_to_host_async()

        meta = CheckpointMeta(
            step=step,
            host_rank=self.host_rank,
            num_hosts=num_hosts,
            mesh_axes=list(getattr(mesh, "axis_names", []) or []),
            mesh_shape=[int(s) for s in getattr(mesh, "devices", np.empty(0)).shape]
            if mesh is not None
            else [],
            timestamp=time.time(),
            extra=extra or {},
        )
        offset = 0
        for rec, _ in plan:
            rec.offset = offset
            offset += rec.nbytes
            meta.records.append(rec)
        meta.total_bytes = offset
        return meta, plan, meta.to_json().encode()

    # -- agent / loader side ----------------------------------------------

    def attach(self) -> bool:
        return self._segment.attach()

    def read_meta(self) -> Optional[CheckpointMeta]:
        if not self._segment.attach():
            return None
        try:
            meta_len = int.from_bytes(self._segment.read(0, HEADER_LEN_BYTES), "little")
            if meta_len <= 0 or meta_len > self._segment.size:
                return None
            return CheckpointMeta.from_json(
                self._segment.read(HEADER_LEN_BYTES, meta_len).decode()
            )
        except Exception:
            logger.exception("unreadable checkpoint shm meta")
            return None

    def payload_reader(
        self, copy: bool = True
    ) -> Optional[Callable[[int, int], Any]]:
        """Reader over the payload region. With ``copy=False`` the reader
        returns zero-copy memoryviews into the segment — valid only while
        the segment stays mapped and unmodified (hold the shard lock)."""
        meta = self.read_meta()
        if meta is None:
            return None
        meta_len = int.from_bytes(self._segment.read(0, HEADER_LEN_BYTES), "little")
        base = HEADER_LEN_BYTES + meta_len

        if copy:

            def read(offset: int, nbytes: int) -> bytes:
                return self._segment.read(base + offset, nbytes)

        else:
            buf = self._segment.buf

            def read(offset: int, nbytes: int):
                return buf[base + offset : base + offset + nbytes]

        return read

    def load_pytree_host(
        self, copy: bool = True
    ) -> Optional[Tuple[CheckpointMeta, Dict[str, np.ndarray]]]:
        """Reassemble {leaf_path: global np array} from this host's shm.

        Only complete when this host holds every shard (single-host case);
        multi-host loads go through the storage/gather paths. With
        ``copy=False``, unsharded leaves are zero-copy views into the
        segment (see :meth:`payload_reader`).
        """
        meta = self.read_meta()
        reader = self.payload_reader(copy=copy)
        if meta is None or reader is None:
            return None
        by_path: Dict[str, List[ShardRecord]] = {}
        for rec in meta.records:
            by_path.setdefault(rec.path, []).append(rec)
        out = {}
        for path, records in by_path.items():
            out[path] = assemble_global(
                records, lambda rec: reader(rec.offset, rec.nbytes)
            )
        return meta, out

    # -- raw segment image (peer replication) ------------------------------

    def image_size(self) -> int:
        """Total bytes of the current segment image, 0 when empty."""
        return segment_image_size(self._segment)

    def read_image(self, offset: int, nbytes: int) -> bytes:
        return self._segment.read(offset, nbytes)

    def write_image_stream(self, total: int, read) -> None:
        """Overwrite this segment with a ``total``-byte image streamed
        from ``read(n)`` (restore-from-peer path). Torn-write safe —
        see :func:`stream_into_segment`."""
        stream_into_segment(self._segment, total, read)

    def invalidate(self) -> None:
        """Zero the header so the staged image reads as absent (e.g. a
        stale peer image that must not be breakpoint-persisted)."""
        if self._segment.attach():
            buf = self._segment.buf
            if buf is not None and len(buf) >= HEADER_LEN_BYTES:
                buf[:HEADER_LEN_BYTES] = b"\x00" * HEADER_LEN_BYTES

    def exists(self) -> bool:
        return self._segment.exists()

    def close(self) -> None:
        self._close_pool()
        self._segment.close()

    def unlink(self) -> None:
        self._close_pool()
        self._segment.unlink()
