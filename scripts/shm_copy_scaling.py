"""How fast host threads copy a checkpoint image into ``/dev/shm``.

    python scripts/shm_copy_scaling.py [--mb 1490] [--threads 1,2,4,8,16]
        [--piece-mib 16] [--sweep-piece-mib 2,4,8,32,64] [--out FILE]

The flash save (``checkpoint/shm_handler.py: save_pytree``) moves the
staged image into a POSIX shared-memory segment; whether several threads
do that faster than one depends on whether the copy statement runs
outside the interpreter lock, and that depends on its form. This prints
one JSON line per (pages, primitive, threads): GB/s, the best of
``--repeats`` for resident pages and one run each for a segment made anew
(``posix_fallocate``d and never touched, as ``SharedMemorySegment``
creates it). Then the same for other piece sizes with ``--sweep-threads``
threads; then single copies that each follow ``--idle-s`` seconds in which
the process slept, as a save follows ten steps in which the host's other
cores had nothing to do (``pages`` reads ``resident_after_idle``: every
reading is printed, none is dropped); and last one line ``{"summary":
...}`` with the tables (the best reading of a cell). It needs no JAX and
no chip, but the numbers are wanted from the chip's host: ``chiprun --
python scripts/shm_copy_scaling.py``.

The source is float32, as the host copies of device arrays are; the
primitives:

- ``slice_fresh_view``: a fresh ``np.frombuffer(buf, uint8, n, off)`` per
  piece and ``view[:] = piece.view(np.uint8)``: ``save_pytree``'s form,
  on one thread up to PR 25 and on several since;
- ``slice_long_view``: one long-lived uint8 view of the segment and one of
  the source, ``dst[lo:hi] = src[lo:hi]``;
- ``copyto``: ``np.copyto`` on the same slices;
- ``memoryview``: ``buf[lo:hi] = src_bytes[lo:hi]`` on memoryviews;
- ``memmove``: ``ctypes.memmove`` on the two addresses;
- ``pwrite``: ``os.pwrite`` of the source piece to the segment's file.

Last, sources that are not C-contiguous (``pages`` reads
``resident_strided``): 36 float32 leaves of shape (768, 3, 12, 64) whose
first axis is minor, as a TPU host hands over GPT-2-small's ``wqkv`` and
its two Adam moments (255 MB), a leaf a task: ``assign_strided`` assigns
the leaf to a view of the segment of its shape, ``contiguous_temp_then_copy``
makes ``np.ascontiguousarray`` of it first (``save_pytree``'s form up to
PR 25).
"""

import argparse
import ctypes
import json
import os
import sys
import threading
import time
from multiprocessing import shared_memory

import numpy as np

MIB = 1 << 20


def make_segment(name: str, size: int) -> shared_memory.SharedMemory:
    shm = shared_memory.SharedMemory(name=name, create=True, size=size)
    os.posix_fallocate(shm._fd, 0, size)  # noqa: SLF001 — as multi_process.py does
    return shm


def drop_segment(shm: shared_memory.SharedMemory) -> None:
    shm.close()
    shm.unlink()


def primitives(shm, src_f32):
    """name -> copy(lo, hi) over byte ranges of the segment and the source."""
    buf = shm.buf
    src_u8 = src_f32.view(np.uint8)
    src_mv = memoryview(src_u8)
    dst_long = np.frombuffer(buf, dtype=np.uint8)
    dst_addr, src_addr = dst_long.ctypes.data, src_u8.ctypes.data
    fd = shm._fd  # noqa: SLF001

    def slice_fresh_view(lo, hi):
        view = np.frombuffer(buf, dtype=np.uint8, count=hi - lo, offset=lo)
        view[:] = src_f32[lo // 4 : hi // 4].view(np.uint8)

    def slice_long_view(lo, hi):
        dst_long[lo:hi] = src_u8[lo:hi]

    def copyto(lo, hi):
        np.copyto(dst_long[lo:hi], src_u8[lo:hi])

    def mview(lo, hi):
        buf[lo:hi] = src_mv[lo:hi]

    def memmove(lo, hi):
        ctypes.memmove(dst_addr + lo, src_addr + lo, hi - lo)

    def pwrite(lo, hi):
        done = lo
        while done < hi:
            done += os.pwrite(fd, src_mv[done:hi], done)

    # the closures keep dst_long alive: the caller drops them before close
    return dict(slice_fresh_view=slice_fresh_view, slice_long_view=slice_long_view,
                copyto=copyto, memoryview=mview, memmove=memmove, pwrite=pwrite)


def pieces_of(total: int, piece: int):
    return [(lo, min(lo + piece, total)) for lo in range(0, total, piece)]


def timed_copy(copy, ranges, threads: int) -> float:
    """Seconds for ``threads`` threads to ``copy(*r)`` every ``r`` of
    ``ranges``, each taking its next from one shared counter."""
    lock, nxt, errors = threading.Lock(), [0], []

    def work():
        try:
            while True:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                if i >= len(ranges):
                    return
                copy(*ranges[i])
        except BaseException as e:  # noqa: BLE001 — reported by the caller
            errors.append(e)
            raise

    workers = [threading.Thread(target=work) for _ in range(threads - 1)]
    t0 = time.perf_counter()
    for w in workers:
        w.start()
    work()  # the calling thread is one of the ``threads``
    for w in workers:
        w.join()
    dur = time.perf_counter() - t0
    if errors:
        raise errors[0]
    return dur


def run_strided(name, thread_counts, repeats, say, n_leaves=36):
    shape = (768, 3, 12, 64)
    leaves = [np.arange(i, i + 768 * 3 * 12 * 64, dtype=np.float32)
              .reshape(3, 12, 64, 768).transpose(3, 0, 1, 2) for i in range(n_leaves)]
    nbytes = leaves[0].nbytes
    shm = make_segment(name, n_leaves * nbytes)
    buf = shm.buf
    try:
        def assign_strided(i):
            dst = np.ndarray(shape, dtype=np.float32, buffer=buf, offset=i * nbytes)
            dst[...] = leaves[i]

        def contiguous_temp_then_copy(i):
            flat = np.ascontiguousarray(leaves[i]).reshape(-1)
            view = np.frombuffer(buf, dtype=np.uint8, count=nbytes, offset=i * nbytes)
            view[:] = flat.view(np.uint8)

        ranges = [(i,) for i in range(n_leaves)]
        timed_copy(assign_strided, ranges, 1)  # touch every page once
        for copy in (assign_strided, contiguous_temp_then_copy):
            for t in thread_counts:
                dur = min(timed_copy(copy, ranges, t) for _ in range(repeats))
                say(pages="resident_strided", primitive=copy.__name__, threads=t,
                    piece_mib=round(nbytes / MIB, 1), seconds=round(dur, 4),
                    gb_per_s=round(n_leaves * nbytes / dur / 1e9, 2))
    finally:
        del buf
        drop_segment(shm)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--mb", type=int, default=1490, help="image size in 10^6 bytes")
    ap.add_argument("--threads", default="1,2,4,8,16")
    ap.add_argument("--piece-mib", type=int, default=16)
    ap.add_argument("--sweep-piece-mib", default="2,4,8,32,64")
    ap.add_argument("--sweep-threads", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--idle-s", type=float, default=2.8)
    ap.add_argument("--idle-primitives", default="slice_fresh_view,memmove")
    ap.add_argument("--only", default="", help="comma list of primitives")
    ap.add_argument("--out", default=None)
    ns = ap.parse_args()

    total = ns.mb * 10**6 // (4 * MIB) * (4 * MIB)  # whole pieces down to 4 MiB
    threads = [int(t) for t in ns.threads.split(",")]
    only = set(filter(None, ns.only.split(",")))
    name = f"shm_copy_scaling_{os.getpid()}"
    src = np.arange(total // 4, dtype=np.float32)  # every page written: resident
    lines = []

    def say(**row):
        lines.append(row)
        print(json.dumps(row), flush=True)

    say(cpus=len(os.sched_getaffinity(0)), cpu_count=os.cpu_count(), total_bytes=total,
        numpy=np.__version__, python=sys.version.split()[0],
        switch_interval_s=sys.getswitchinterval())

    def run(pages, piece_mib, thread_counts, names=None):
        piece = piece_mib * MIB
        shm = make_segment(name, total)
        try:
            prims = primitives(shm, src)
            names = [n for n in prims if n in (names or only or prims)]
            if pages != "first_touch":
                prims["memmove"](0, total)  # touch every page once
            for prim in names:
                for t in thread_counts:
                    if pages == "resident":
                        dur = min(timed_copy(prims[prim], pieces_of(total, piece), t)
                                  for _ in range(ns.repeats))
                    elif pages == "resident_after_idle":
                        time.sleep(ns.idle_s)
                        dur = timed_copy(prims[prim], pieces_of(total, piece), t)
                    else:
                        # a segment made anew for every reading
                        del prims
                        drop_segment(shm)
                        shm = make_segment(name, total)
                        prims = primitives(shm, src)
                        dur = timed_copy(prims[prim], pieces_of(total, piece), t)
                    say(pages=pages, primitive=prim, threads=t, piece_mib=piece_mib,
                        seconds=round(dur, 4), gb_per_s=round(total / dur / 1e9, 2))
            del prims
        finally:
            drop_segment(shm)

    run("resident", ns.piece_mib, threads)
    run("first_touch", ns.piece_mib, threads)
    sweep = [int(p) for p in ns.sweep_piece_mib.split(",") if p]
    for piece_mib in sweep:
        run("resident", piece_mib, [ns.sweep_threads])
    if ns.idle_s > 0:
        for _ in range(ns.repeats):
            run("resident_after_idle", ns.piece_mib, threads,
                set(ns.idle_primitives.split(",")))

    run_strided(name, threads, ns.repeats, say)

    table = {}
    for row in lines[1:]:
        key = f"{row['pages']}/{row['primitive']}/piece{row['piece_mib']}"
        cell = table.setdefault(key, {})
        cell[row["threads"]] = max(cell.get(row["threads"], 0.0), row["gb_per_s"])
    say(summary=table)
    if ns.out:
        os.makedirs(os.path.dirname(os.path.abspath(ns.out)), exist_ok=True)
        with open(ns.out, "w") as f:
            for row in lines:
                f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
