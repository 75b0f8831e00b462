"""Trainer-side checkpoint engine for jax pytrees.

Reference: ``CheckpointEngine`` (``flash_checkpoint/engine.py:154``) — the
in-training-process half: ``save_to_memory`` (blocking sub-second),
``save_to_storage`` (hand off to the agent saver), ``load`` (memory first,
storage fallback). One engine covers DDP/FSDP/TP cases uniformly because
the shard topology is derived from each leaf's jax sharding rather than
from a framework-specific engine subclass (reference needed
full/fsdp/megatron engines; SURVEY.md §2.4).
"""

import os
import resource
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..chaos import faults
from ..common.constants import NodeEnv
from ..common.log import logger
from ..common.multi_process import LocalSocketClient, SharedLock, SharedQueue
from ..common.events import TrainerEvents
from ..observability.spans import process_accumulator, span
from .saver import (
    EVENT_QUEUE,
    FACTORY_QUEUE,
    AsyncCheckpointSaver,
    CheckpointEvent,
    lock_name,
)
from .shm_handler import SharedMemoryHandler
from .storage import PosixCheckpointStorage


def _restore_into_template(template: Any, arrays: Dict[str, np.ndarray]) -> Any:
    """Map {path: global np array} back onto the template pytree, placing
    each leaf with the template leaf's sharding (re-mesh happens here: the
    saved mesh may differ from the template's — device_put reshards).

    All device leaves go through ONE batched ``jax.device_put`` call: a
    per-leaf loop costs a dispatch round trip per leaf (~450 for a GPT-2
    train state), which dominated restore time in round 1
    (BENCH_r01 restore_s=21.4 for 1.5 GB ≈ 70 MB/s).
    """
    from .shm_handler import _path_str

    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves: list = [None] * len(flat)
    host_arrs, shardings, positions = [], [], []
    for i, (path, leaf) in enumerate(flat):
        key = _path_str(path)
        if key not in arrays:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = arrays[key]
        if isinstance(leaf, jax.Array):
            if str(arr.dtype) != str(leaf.dtype):
                arr = arr.astype(leaf.dtype)
            host_arrs.append(arr)
            shardings.append(leaf.sharding)
            positions.append(i)
        else:
            # Force a copy: `arr` may be a zero-copy view into shm whose
            # lifetime ends when the caller releases the shard lock.
            leaves[i] = np.array(arr, dtype=getattr(leaf, "dtype", arr.dtype))
    if host_arrs:
        placed = jax.device_put(host_arrs, shardings)
        jax.block_until_ready(placed)
        for i, p in zip(positions, placed):
            leaves[i] = p
    return jax.tree_util.tree_unflatten(treedef, leaves)


_SAVE_PARTS = ("plan", "ensure", "d2h", "memcpy")


def _save_parts_since(before: Dict[str, float]) -> Dict[str, float]:
    """Seconds each part of ``shm.save_pytree`` booked since ``before``
    (``process_accumulator().totals()``): what the ``ckpt_save`` event
    carries at its end, so that every save of every run, traced or not,
    says where its time went (a slow one rarely falls into a traced window)."""
    now = process_accumulator().totals()
    return {
        f"{part}_s": round(
            now.get(f"ckpt.save.{part}", 0.0)
            - before.get(f"ckpt.save.{part}", 0.0), 6
        )
        for part in _SAVE_PARTS
    }


def _minor_faults() -> int:
    """Pages this process has had to be given so far, all threads."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_minflt


def _device_memory_stats(device) -> Optional[Dict[str, int]]:
    """What the backend reports for ``device`` (None on the CPU)."""
    return device.memory_stats()


def _process_count() -> int:
    """World size WITHOUT initializing a jax backend: the engine also
    runs inside non-JAX workers (torch family), where jax.process_count()
    would boot a hardware plugin just to answer "1" — and hang if the
    accelerator is unreachable. jax.distributed.initialize records the
    world in the distributed global state; absent that, we are single
    process by definition."""
    # private module, checked against the installed jax 0.9.0
    from jax._src import distributed

    return int(distributed.global_state.num_processes or 1)


class CheckpointEngine:
    def __init__(
        self,
        checkpoint_dir: str,
        mesh=None,
        host_rank: Optional[int] = None,
        num_hosts: Optional[int] = None,
        master_client=None,
        standalone: Optional[bool] = None,
        replicate: Optional[bool] = None,
        replica_peers: Optional[Dict[int, str]] = None,
        saver_timeout_s: Optional[float] = None,
        prefetch_restore: Optional[bool] = None,
        durable_dir: Optional[str] = None,
        durable_lineage: Optional[str] = None,
    ):
        self.checkpoint_dir = checkpoint_dir
        self.mesh = mesh
        # Durable tier (checkpoint/durable/): None → Context knobs, so
        # production jobs configure via DLROVER_DURABLE_* while tests
        # and warm-pool callers pass explicit values.
        if durable_dir is None or durable_lineage is None:
            from ..common.config import get_context

            _ctx = get_context()
            if durable_dir is None:
                durable_dir = _ctx.durable_dir
            if durable_lineage is None:
                durable_lineage = _ctx.durable_lineage
        self.durable_dir = durable_dir or ""
        self.durable_lineage = (
            durable_lineage
            or os.environ.get("DLROVER_JOB_NAME", "")
            or "default"
        )
        self.host_rank = (
            host_rank
            if host_rank is not None
            else int(os.getenv(NodeEnv.PROCESS_ID, "0"))
        )
        self.num_hosts = (
            num_hosts
            if num_hosts is not None
            else int(os.getenv(NodeEnv.NUM_PROCESSES, "1"))
        )
        self.master_client = master_client
        self.storage = PosixCheckpointStorage(checkpoint_dir)
        self.shm = SharedMemoryHandler(self.host_rank)
        self._events = TrainerEvents()
        self._latest_storage_step = -1
        # Peer-memory replication (reference replica.py): on by default
        # for multi-host jobs; each memory save is mirrored into a backup
        # host's memory by the agent saver, and load() can recover this
        # host's shard from a peer when the node was replaced.
        self._replicate = (
            replicate if replicate is not None else self.num_hosts > 1
        )
        self._replica_peers = replica_peers

        # How long to wait for the saver's shard-lock server before
        # declaring its IPC wedged (chaos tests shorten this; the
        # default matches the old hard-coded 30 s).
        self._saver_timeout_s = (
            saver_timeout_s
            if saver_timeout_s is not None
            else float(os.getenv("DLROVER_CKPT_SAVER_TIMEOUT_S", "30"))
        )
        if standalone is None:
            standalone = not LocalSocketClient("queue_" + FACTORY_QUEUE).available()
        self._standalone = standalone
        if standalone:
            # No agent supervising us (reference start_saver_process
            # fallback, engine.py:118): run the saver in-process.
            self._saver_thread = AsyncCheckpointSaver.start_async_saving_ckpt()
        # A persist-error marker surviving from a PREVIOUS incarnation is
        # stale history (e.g. disk-full fixed, job resumed at a lower
        # step): left in place it would fail-fast every wait_saving of
        # the new run whose steps sit below the old failed step.
        self.storage.clear_persist_error(self.host_rank)
        self._factory_q = SharedQueue(FACTORY_QUEUE)
        self._event_q = SharedQueue(EVENT_QUEUE)
        self._factory_q.put(self._factory_msg())
        try:
            self._shard_lock = self._wait_lock(self._saver_timeout_s)
        except TimeoutError:
            if self._standalone:
                raise  # our own in-process saver failed: nothing to fall to
            self._fallback_standalone_saver()
        # Async staging (save_to_memory(block=False)): the trainer's
        # blocking cost is one device-side snapshot dispatch; a
        # background thread does the D2H + shm memcpy and releases the
        # shard lock when done.
        self._stage_thread: Optional[threading.Thread] = None
        self._stage_error: Optional[BaseException] = None
        self._snap_fn = None
        # Async staging needs ~+1x the state's bytes of free HBM for
        # the snapshot window. If the device can't afford it, the first
        # attempt fails RESOURCE_EXHAUSTED and all later block=False
        # saves transparently degrade to the blocking path.
        self._async_disabled = False
        self._headroom_logged = False
        # Overlapped restore (warm-restart fast path, docs/recovery.md):
        # the host-side half of the restore — shm attach + copy-out, or
        # the peer replica fetch when this host's shm is empty
        # (replica-first ordering for a replaced node) — starts NOW, in
        # the background, so it overlaps whatever runs between engine
        # construction and load()/load_consistent() (model build, train
        # step compile, the restore-source agreement's allgather). The
        # restore call then pays only the fused host→device put.
        self._prefetched: Optional[Tuple[Any, Dict[str, np.ndarray]]] = None
        self._prefetch_thread: Optional[threading.Thread] = None
        self._prefetch_invalid = False
        self.prefetch_used = False  # last restore consumed the prefetch
        if prefetch_restore is None:
            from ..common.config import get_context

            prefetch_restore = get_context().ckpt_prefetch_restore
        if prefetch_restore:
            self._prefetch_thread = threading.Thread(
                target=self._prefetch_restore_host,
                name="ckpt-restore-prefetch",
                daemon=True,
            )
            self._prefetch_thread.start()

    def _factory_msg(self) -> Dict:
        return {
            "type": "create",
            "storage_root": self.checkpoint_dir,
            "host_rank": self.host_rank,
            "num_hosts": self.num_hosts,
            "replicate": self._replicate,
            "replica_peers": self._replica_peers,
            "durable_dir": self.durable_dir,
            "durable_lineage": self.durable_lineage,
        }

    def _wait_lock(self, timeout: float = 30.0) -> SharedLock:
        deadline = time.time() + timeout
        lock = SharedLock(lock_name(self.host_rank))
        while not lock._client.available():
            if time.time() > deadline:
                raise TimeoutError("checkpoint saver did not come up")
            time.sleep(0.05)
        return lock

    def _fallback_standalone_saver(self) -> None:
        """The agent saver's IPC is wedged: its factory socket accepted
        our create message (``available()`` said yes) but the shard-lock
        server never came up within ``saver_timeout_s``. Checkpointing
        must not die with it — re-point this process at a FRESH private
        IPC namespace and run an in-process saver there. The wedged
        namespace's sockets/shm are left to the wedged owner; staging
        restarts clean in the fallback namespace (memory restore of the
        old incarnation's image is sacrificed — storage history, which
        the fallback saver keeps writing, is not)."""
        from ..common.multi_process import _ipc_namespace

        old_ns = _ipc_namespace()
        fresh_ns = f"{old_ns}_fb{os.getpid()}"
        logger.error(
            "checkpoint saver IPC wedged (no shard lock within %.0fs); "
            "falling back to a standalone saver in fresh namespace %s",
            self._saver_timeout_s,
            fresh_ns,
        )
        for res in (self._factory_q, self._event_q):
            try:
                res.close()
            except Exception as e:  # noqa: BLE001 — old namespace, best effort
                logger.debug("closing old-namespace IPC resource: %r", e)
        self.shm.close()
        os.environ["DLROVER_IPC_NAMESPACE"] = fresh_ns
        self.shm = SharedMemoryHandler(self.host_rank)
        self._standalone = True
        self._saver_thread = AsyncCheckpointSaver.start_async_saving_ckpt()
        self._factory_q = SharedQueue(FACTORY_QUEUE)
        self._event_q = SharedQueue(EVENT_QUEUE)
        self._factory_q.put(self._factory_msg())
        self._shard_lock = self._wait_lock(self._saver_timeout_s)

    # -- overlapped restore ------------------------------------------------

    def _read_staged_host(
        self, timeout: float = 60.0
    ) -> Optional[Tuple[Any, Dict[str, np.ndarray]]]:
        """(meta, arrays) copied out of shm under the shard lock, or
        None when there is no readable image."""
        if not self._shard_lock.acquire(blocking=True, timeout=timeout):
            return None
        try:
            if not self.shm.attach():
                return None
            return self.shm.load_pytree_host(copy=True)
        finally:
            self._shard_lock.release()

    def _prefetch_restore_host(self) -> None:
        """Background half of the overlapped restore: read this host's
        staged image out of shm — or, when shm is empty, pull the
        replica of this host's shard from its backup peer FIRST (the
        replaced-node case, where the peer fetch is the expensive part)
        — so the foreground restore call finds the host bytes ready."""
        try:
            got = self._read_staged_host(timeout=30.0)
            # A save (or close) sets _prefetch_invalid to CANCEL this
            # thread: never start the peer fetch afterwards — a late
            # refill would overwrite shm with a replica OLDER than the
            # step the save is about to stage.
            if (
                got is None
                and not self._prefetch_invalid
                and self._replicate
                and self._refill_from_peer()
                and not self._prefetch_invalid
            ):
                got = self._read_staged_host(timeout=30.0)
            self._prefetched = got
        except Exception as e:  # noqa: BLE001 — an optimization only
            logger.warning("restore prefetch failed: %s", e)

    def _restore_from_prefetch(
        self, template: Any, pre: Optional[Tuple[Any, Dict[str, np.ndarray]]]
    ) -> Optional[Tuple[int, Any]]:
        """Place a consumed prefetch onto the device — the one restore
        path shared by load() and load_consistent(). None when there is
        no prefetch or the image does not fit ``template`` (callers
        fall through to the locked re-read)."""
        if pre is None:
            return None
        meta, arrays = pre
        try:
            restored = _restore_into_template(template, arrays)
        except (KeyError, ValueError) as e:
            logger.warning("prefetched image unusable (%s); re-reading", e)
            return None
        self.prefetch_used = True
        logger.info("restored step %s from prefetched host read", meta.step)
        return meta.step, restored

    def _consume_prefetch(
        self,
    ) -> Optional[Tuple[Any, Dict[str, np.ndarray]]]:
        """Join the prefetch and hand over its result — None when it is
        disabled, still running, empty, or invalidated by a save that
        restaged the segment after the prefetch read it."""
        t = self._prefetch_thread
        if t is not None:
            t.join(60.0)
            if t.is_alive():
                logger.warning(
                    "restore prefetch still running; ignoring its result"
                )
                self._prefetch_invalid = True
            self._prefetch_thread = None
        got, self._prefetched = self._prefetched, None
        if self._prefetch_invalid or got is None:
            return None
        return got

    # -- save --------------------------------------------------------------

    def _all_hosts_ready(self, ready: bool) -> bool:
        """All-or-none gate for a multi-process save (reference
        ``check_all_rank_ready`` allreduce, engine.py:57-71): if ANY
        host's persister holds its shard lock, every host skips this
        step. Without it hosts stage DIFFERENT steps over time and a
        re-meshed world has no common memory step to resume from."""
        if _process_count() <= 1:
            return ready
        from jax.experimental import multihost_utils

        all_ready = multihost_utils.process_allgather(
            np.int64(1 if ready else 0)
        )
        return bool(np.all(all_ready))

    def save_to_memory(
        self,
        step: int,
        pytree: Any,
        extra: Optional[Dict] = None,
        block: bool = True,
        for_storage: bool = False,
    ) -> bool:
        """Stage the pytree into host shm. Skips (returns False) if ANY
        host's persister still holds its shard lock (reference
        non-blocking acquire + all-rank-ready allreduce,
        engine.py:57-71,351-365) — all-or-none, so every host's shm
        always stages the SAME step.

        ``block=True`` blocks for D2H + memcpy (sub-second at HBM/shm
        bandwidth). ``block=False`` blocks only to DISPATCH a
        device-side snapshot (an HBM-bandwidth copy this engine owns —
        NOTE: the snapshot holds ~+1x the state's bytes in HBM until
        staging drains; a device without that headroom OOMs the first
        attempt, which permanently degrades block=False to the blocking
        path for this engine):
        the train step donates its state buffers
        (``train_step.py:donate``), so staging must not read them after
        the trainer's next dispatch — ``copy_to_host_async`` alone does
        NOT survive donation (the array is marked deleted). A background
        thread then streams the snapshot to host shm and releases the
        shard lock; the lock serializes it against the persister and
        cross-process readers. The next save from THIS engine must be
        guarded separately — the shard lock is reentrant per owner
        (same pid+object), so an in-flight staging thread would not
        block a sibling acquire — hence the explicit thread-alive skip,
        folded into the all-hosts allreduce so every host skips the
        same step together.
        """
        # Chaos hook: a delay here stretches the trainer's blocking
        # window; an error must surface to the loop (which re-saves
        # blocking or skips the step), never wedge the shard lock.
        faults.inject("ckpt.engine.save", step=step)
        # The save names its own time on the profiler's clock. A root
        # this rare carries the wall clock too: the trace's timestamps
        # count from the session's start, events carry wall-clock ``ts``,
        # and ``unix_ns`` is what puts the two on one axis.
        with span("ckpt.save", step=step, unix_ns=time.time_ns()) as root:
            return self._save_to_memory(
                step, pytree, extra, block, for_storage, root
            )

    def _save_to_memory(
        self, step, pytree, extra, block, for_storage, root
    ) -> bool:
        with span("ckpt.save.ready"):
            ready, acquired = self._ready_to_save(step)
        if not ready:
            if acquired:
                self._shard_lock.release()
            logger.warning(
                "skip save_to_memory step %s: a persister is busy", step
            )
            return False
        if not block and self._async_disabled:
            block = True
        if not block:
            with span("ckpt.save.snapshot") as snap:
                snapshot = None
                if self._snapshot_fits(pytree):
                    try:
                        snapshot = self._snapshot(pytree)
                    except Exception as e:
                        msg = repr(e).lower()
                        if not (
                            "resource_exhausted" in msg
                            or "out of memory" in msg
                        ):
                            self._shard_lock.release()
                            raise
                        # No HBM headroom for the snapshot: degrade THIS
                        # and all later saves to the blocking path (we
                        # still hold the shard lock — fall through).
                        self._async_disabled = True
                        logger.error(
                            "snapshot OOM at step %s; degrading to "
                            "blocking saves", step
                        )
                snap.set(fits=int(snapshot is not None))
            # no snapshot: no HBM headroom for a device-side copy
            block = snapshot is None
        root.set(blocking=int(block))
        if not block:
            try:
                t = threading.Thread(
                    target=self._stage_async,
                    args=(step, snapshot, extra, for_storage),
                    name=f"ckpt-stage-{step}",
                    daemon=True,
                )
                t.start()
                # Assigned only AFTER start(): join() on a never-started
                # thread raises, which would break every later
                # wait_staged/close if start() itself failed.
                self._stage_thread = t
                return True
            except Exception:
                self._shard_lock.release()
                raise
        try:
            self._stage_into_shm(step, pytree, extra, root)
            # A successful blocking save supersedes any stale async
            # failure: without this, a degraded (async-disabled) engine
            # would keep failing wait_staged_all and force redundant
            # re-saves of steps that already landed.
            self._stage_error = None
        finally:
            self._shard_lock.release()
        if self._replicate:
            # Mirror to the backup peer — handled by the agent saver so
            # the trainer never blocks on a DCN transfer.
            self._event_q.put({"type": CheckpointEvent.REPLICATE, "step": step})
        return True

    def _stage_into_shm(self, step: int, tree: Any, extra, root) -> None:
        """``shm.save_pytree`` under the ``ckpt_save`` event, whose end
        carries the split, on how many threads the payload was copied
        and ``minor_faults``: the pages the process was given meanwhile,
        which are few where the host copies land in memory the allocator
        kept from the last save (``ElasticLaunchConfig.worker_env``) and
        one a page of the image where they do not. ``root`` (the open
        ``ckpt.save`` or ``ckpt.stage`` span) gets what was staged."""
        with self._events.ckpt_save(step, storage="memory") as event:
            before = process_accumulator().totals()
            faults_before = _minor_faults()
            meta = self.shm.save_pytree(
                step,
                tree,
                num_hosts=self.num_hosts,
                mesh=self.mesh,
                extra=extra,
            )
            minor_faults = _minor_faults() - faults_before
            event.content.update(
                _save_parts_since(before),
                copy_threads=self.shm.copy_threads,
                minor_faults=minor_faults,
            )
        root.set(
            bytes=meta.total_bytes,
            leaves=len(meta.records),
            minor_faults=minor_faults,
        )

    def _ready_to_save(self, step: int) -> Tuple[bool, bool]:
        """Everything a save waits for before it touches the state:
        the restore prefetch, the shard lock, the other hosts. Returns
        (all hosts ready, this host holds the shard lock)."""
        # Any save supersedes the restore prefetch: a later consume of
        # the pre-save image would silently restore an older step.
        # Invalid FIRST — it doubles as the cancel signal, so a thread
        # that has not yet started its peer fetch skips it instead of
        # stalling this save (a saving host's state is newer than any
        # replica of it). Then wait the remainder out: the prefetch
        # briefly holds the shard lock and the non-blocking acquire
        # below must not misread the init-time read as "persister busy"
        # and skip the step.
        self._prefetch_invalid = True
        self._prefetched = None
        pt = self._prefetch_thread
        if pt is not None and pt.is_alive():
            pt.join(30.0)
        staging = self._stage_thread is not None and self._stage_thread.is_alive()
        if staging:
            logger.warning(
                "step %s: previous async stage still in flight", step
            )
        acquired = (not staging) and self._shard_lock.acquire(blocking=False)
        try:
            ready = self._all_hosts_ready(acquired)
        except Exception:
            # a peer died mid-allgather: surface it, but NEVER while
            # holding the shard lock — a leaked lock starves the agent
            # persister forever
            if acquired:
                self._shard_lock.release()
            raise
        return ready, acquired

    def _snapshot_fits(self, pytree: Any) -> bool:
        """Whether every device has room for a second copy of its share
        of ``pytree`` BESIDE the largest program it has run. The
        snapshot outlives the dispatch of the next step (staging streams
        it out meanwhile), and XLA reserves a step's temporaries as one
        block when it loads the program: a snapshot that fit between two
        steps makes the NEXT step fail to load (seen on the v5e with
        GPT-2-small at b32: "Attempting to reserve 13.25G ... 12.97G
        free"). The reservation is not part of ``bytes_in_use``; its
        high-water mark is ``peak_bytes_reserved``. A backend that
        reports no memory stats (CPU) is taken to have room."""
        need: Dict[Any, int] = {}
        for leaf in jax.tree_util.tree_leaves(pytree):
            if isinstance(leaf, jax.Array):
                for shard in leaf.addressable_shards:
                    need[shard.device] = (
                        need.get(shard.device, 0) + shard.data.nbytes
                    )
        for device, nbytes in need.items():
            stats = _device_memory_stats(device)
            if not stats or "bytes_limit" not in stats:
                continue
            headroom = (
                stats["bytes_limit"]
                - stats.get("peak_bytes_reserved", 0)
                - stats.get("bytes_in_use", 0)
            )
            if nbytes > headroom:
                if not self._headroom_logged:
                    self._headroom_logged = True
                    logger.warning(
                        "async staging off: %s needs %.2f GiB for a "
                        "snapshot, %.2f GiB free beside its largest "
                        "program; saves block on D2H instead",
                        device,
                        nbytes / 2**30,
                        headroom / 2**30,
                    )
                return False
        return True

    def _snapshot(self, pytree: Any) -> Any:
        """Device-side copy of every jax leaf in ONE jitted dispatch
        (fresh buffers — ``jnp.copy`` lowers to an explicit copy that
        cannot alias its input), host leaves copied on host. The result
        is immune to the caller donating/overwriting the originals."""
        import jax.numpy as jnp

        flat, treedef = jax.tree_util.tree_flatten(pytree)
        is_dev = [isinstance(leaf, jax.Array) for leaf in flat]
        dev_leaves = [l for l, d in zip(flat, is_dev) if d]
        if dev_leaves:
            if self._snap_fn is None:
                self._snap_fn = jax.jit(
                    lambda leaves: [jnp.copy(l) for l in leaves]
                )
            dev_copies = iter(self._snap_fn(dev_leaves))
        else:
            dev_copies = iter(())
        out = [
            next(dev_copies)
            if d
            else (np.array(l, copy=True) if isinstance(l, np.ndarray) else l)
            for l, d in zip(flat, is_dev)
        ]
        return jax.tree_util.tree_unflatten(treedef, out)

    def _stage_async(self, step: int, snapshot: Any, extra, for_storage: bool) -> None:
        """Background half of save_to_memory(block=False). Owns the
        already-acquired shard lock; ALWAYS releases it. ``_stage_error``
        is sticky across saves until a stage SUCCEEDS (or wait_staged
        consumes it): the loop's boundary checks turn it into a blocking
        re-save, where the silent alternative loses the step."""
        ok = False
        try:
            # the staging thread's root: the save's own root (ckpt.save)
            # closed on the trainer's thread when this one started
            with span(
                "ckpt.stage", step=step, unix_ns=time.time_ns()
            ) as root:
                self._stage_into_shm(step, snapshot, extra, root)
            ok = True
            self._stage_error = None
        except BaseException as e:  # noqa: BLE001 — recorded, surfaced by wait_staged
            self._stage_error = e
            logger.error("async checkpoint staging failed at step %s: %s", step, e)
            msg = repr(e).lower()
            if "resource_exhausted" in msg or "out of memory" in msg:
                self._async_disabled = True
                logger.error(
                    "no HBM headroom for snapshot staging; later saves "
                    "fall back to blocking D2H"
                )
            if for_storage:
                # The SAVE event is already queued; the persister will
                # find an absent image and skip. Leave a persist-error
                # marker so wait_saving fails FAST instead of burning
                # its whole timeout on a step that will never commit.
                try:
                    self.storage.record_persist_error(
                        self.host_rank, step, f"async stage failed: {e!r}"
                    )
                except Exception as rec_err:  # noqa: BLE001
                    logger.warning(
                        "could not record persist error for step %s: %r",
                        step,
                        rec_err,
                    )
        finally:
            self._shard_lock.release()
        if ok and self._replicate:
            self._event_q.put({"type": CheckpointEvent.REPLICATE, "step": step})

    def wait_staged_all(self, timeout: float = 300.0) -> bool:
        """Collective wait_staged: ANDs every host's local outcome via
        the same allgather as ``_all_hosts_ready``. The train loop gates
        COLLECTIVE decisions (blocking re-save before a re-mesh, final
        re-save) on the staging verdict — a per-host verdict would send
        hosts down different code paths and wedge the world's collective
        sequence (one host in save_to_memory's allgather, another in
        remesh). Call points must themselves be collective-aligned."""
        ok = self.wait_staged(timeout)
        if _process_count() <= 1:
            return ok
        from jax.experimental import multihost_utils

        all_ok = multihost_utils.process_allgather(np.int64(1 if ok else 0))
        return bool(np.all(all_ok))

    def _drain_stage_for_read(self) -> None:
        """Gate every restore path on the staging thread being DEAD —
        not merely timed out. A wedged stage thread still writes through
        the reentrant shard lock; proceeding would let a second writer
        (peer refill) interleave on the same segment, which the
        header-last protocol cannot protect against. A dead thread with
        a recorded failure is fine: the zeroed/absent header parses as
        no-image and load falls through to peer/storage."""
        t = self._stage_thread
        if t is not None and t.is_alive():
            t.join(300.0)
            if t.is_alive():
                raise RuntimeError(
                    "async checkpoint staging is wedged (>300s); refusing "
                    "to restore over a live writer on the shm segment"
                )
        self.wait_staged(timeout=0.1)

    def wait_staged(self, timeout: float = 300.0) -> bool:
        """Join the outstanding async staging, if any. Returns False if
        it failed or is still running at the deadline. A recorded
        failure is CONSUMED here: the caller reacts (the loop re-saves
        blocking), so a later wait must not keep reporting it."""
        t = self._stage_thread
        if t is not None:
            t.join(timeout)
            if t.is_alive():
                return False
            self._stage_thread = None
        err, self._stage_error = self._stage_error, None
        return err is None

    def save_to_storage(
        self,
        step: int,
        pytree: Any,
        extra: Optional[Dict] = None,
        block: bool = True,
    ) -> bool:
        """Stage to memory, then hand persistence to the agent saver.
        With ``block=False`` the SAVE event is enqueued while staging
        still runs — safe because the persister must take the shard
        lock, which the staging thread holds until the image is
        complete."""
        if not self.save_to_memory(
            step, pytree, extra, block=block, for_storage=True
        ):
            return False
        self._event_q.put({"type": CheckpointEvent.SAVE, "step": step})
        self._latest_storage_step = step
        return True

    def wait_saving(self, timeout: float = 300.0) -> bool:
        """Block until the queued *storage* saves are persisted (tracker
        catches up). Memory-only saves don't gate this — they have no
        pending disk work.

        Fails fast (no full-timeout stall) when the saver reported a
        persist error for this shard or its event-queue server vanished
        (saver process crashed)."""
        if self._latest_storage_step < 0:
            return True
        deadline = time.time() + timeout
        while time.time() < deadline:
            latest = self.storage.latest_step()
            # NOT `latest or -1`: a committed step 0 is falsy and the
            # idiom would spin out the whole timeout on the first save.
            if latest is not None and latest >= self._latest_storage_step:
                return True
            err = self.storage.persist_error(self.host_rank)
            if err is not None and err[0] >= self._latest_storage_step:
                # Markers from OLDER steps are stale history — a newer
                # save is in flight and may well succeed.
                logger.error(
                    "saver reported persist failure at step %s: %s",
                    err[0],
                    err[1],
                )
                return False
            if not self._event_q.available():
                # Re-check the tracker once: the saver may have committed
                # and exited between our two probes.
                latest = self.storage.latest_step()
                if latest is not None and latest >= self._latest_storage_step:
                    return True
                logger.error(
                    "checkpoint saver is gone (event queue unreachable); "
                    "step %s will not be persisted",
                    self._latest_storage_step,
                )
                return False
            time.sleep(0.1)
        return False

    # -- load --------------------------------------------------------------

    def load(self, template: Any) -> Tuple[int, Optional[Any]]:
        """Restore into ``template``'s structure/shardings: own host
        memory first, then a peer's replica of this host's shard
        (node-replacement recovery without touching storage — reference
        engine.py:375,392-409), then storage.

        Returns (step, restored_pytree) or (-1, None) if nothing to load.
        """
        faults.inject("ckpt.engine.load", host_rank=self.host_rank)
        # Drain any in-flight async stage first: the shard lock is
        # reentrant for this engine, so _load_from_memory would NOT
        # block on the staging thread and could read a half-written
        # image.
        self._drain_stage_for_read()
        with self._events.ckpt_load():
            pre = self._consume_prefetch()
            result = self._restore_from_prefetch(template, pre)
            if result is not None:
                return result
            result = self._load_from_memory(template)
            if result is not None:
                return result
            result = self._load_from_peer(template)
            if result is not None:
                return result
            result = self._load_from_storage(template)
            if result is not None:
                return result
            result = self._load_from_durable(template)
            if result is not None:
                return result
        return -1, None

    def load_resharded(
        self, mesh, step: Optional[int] = None
    ) -> Tuple[int, Optional[Dict[str, Any]], Dict[str, Any]]:
        """Templateless restore of the staged flash image under ``mesh``
        — the in-memory rung transition of the elastic replanner
        (docs/elastic_parallelism.md).

        Unlike :meth:`load`, there is no template state to borrow
        shardings from: the OLD world's programs are gone (the new rung
        has different mesh extents), so each leaf's target sharding is
        derived from its RESHARD_RULES category + the spec stamped into
        the shm image at save time — the same
        ``place_arrays_with_rules`` engine the durable tier's
        reshard-on-read restore drives. Returns ``(step, {leaf path:
        placed array}, extra)`` or ``(-1, None, {})`` when shm holds no
        image (or ``step`` was given and the image is a different
        step — the caller wants THIS step's state, not whatever is
        lying around).
        """
        from ..parallel.sharding import place_arrays_with_rules

        faults.inject("ckpt.engine.load", host_rank=self.host_rank)
        self._drain_stage_for_read()
        with self._events.ckpt_load():
            got = self._read_staged_host()
            if got is None:
                return -1, None, {}
            meta, arrays = got
            if step is not None and meta.step != step:
                logger.warning(
                    "staged image holds step %s, wanted %s; not resharding",
                    meta.step,
                    step,
                )
                return -1, None, {}
            saved_specs = {rec.path: rec.spec for rec in meta.records}
            placed = place_arrays_with_rules(saved_specs, arrays, mesh)
        logger.info(
            "resharded step %s from host memory onto mesh %s (%s leaves)",
            meta.step,
            dict(getattr(mesh, "shape", {})),
            len(placed),
        )
        return meta.step, placed, dict(meta.extra)

    def _refill_from_peer(self) -> bool:
        """Pull this host's replicated shard from its backup peer into
        local shm (control-plane transfer only — NO device collectives,
        so it is safe before a multi-process restore agreement). True
        when shm now holds a usable image."""
        if not self._replicate:
            return False
        from .replica import ReplicaManager, default_master_client

        client = self.master_client
        if client is None and self._replica_peers is None:
            client = default_master_client()
            if client is None:
                return False
        manager = ReplicaManager(
            self.host_rank,
            self.num_hosts,
            master_client=client,
            peers=self._replica_peers,
        )
        if not self._shard_lock.acquire(blocking=True, timeout=60.0):
            manager.stop()
            return False
        try:
            # Staleness check BEFORE the expensive host->device restore:
            # a replica can lag behind storage (push failures are
            # log-and-drop), and restoring a multi-GB pytree only to
            # throw it away wastes minutes on the recovery path.
            return manager.refill_shm(self.shm, self.storage) == "refilled"
        finally:
            self._shard_lock.release()
            manager.stop()

    def _load_from_peer(self, template: Any):
        """Refill this host's shm from the peer that replicated it, then
        load through the normal memory path."""
        if not self._refill_from_peer():
            return None
        return self._load_from_memory(template)

    def _load_from_memory(self, template: Any):
        # Everything happens under the shard lock: the persister (or a
        # dying trainer's last save) may be mid-write. The load COPIES
        # out of the segment (copy=True): zero-copy views were tried and
        # leak — on the CPU backend jax.device_put aliases the host
        # buffer, so a view into the mmap outlives the lock scope and
        # the segment can never be closed (BufferError: cannot close
        # exported pointers exist). One memcpy at memory bandwidth is
        # cheap next to the device transfer it feeds.
        if not self._shard_lock.acquire(blocking=True, timeout=60.0):
            logger.warning("shard lock busy; skipping memory restore")
            return None
        try:
            if not self.shm.attach():
                return None
            got = self.shm.load_pytree_host(copy=True)
            if got is None:
                return None
            meta, arrays = got
            try:
                restored = _restore_into_template(template, arrays)
            except (KeyError, ValueError) as e:
                logger.warning(
                    "memory checkpoint unusable (%s); trying storage", e
                )
                return None
        finally:
            self._shard_lock.release()
        logger.info("restored step %s from host memory", meta.step)
        return meta.step, restored

    def _load_from_storage(self, template: Any, step: Optional[int] = None):
        if step is None:
            step = self.storage.latest_step()
        if step is None:
            return None
        arrays = self.storage.load_step_host(step)
        if arrays is None:
            return None
        try:
            restored = _restore_into_template(template, arrays)
        except (KeyError, ValueError) as e:
            logger.warning(
                "storage checkpoint step %s unusable (%s); starting fresh",
                step,
                e,
            )
            return None
        logger.info("restored step %s from storage %s", step, self.checkpoint_dir)
        return step, restored

    def _load_from_durable(self, template: Any, step: Optional[int] = None):
        """Last rung of the restore chain: the durable tier
        (``checkpoint/durable/``). The generation may have been written
        by a DIFFERENT world — world size and axis layout both — so
        this is a reshard-on-read: saved specs are validated against
        RESHARD_RULES, the global arrays are assembled from all saved
        shards, and the template's current-mesh shardings place them."""
        if not self.durable_dir:
            return None
        try:
            from ..parallel.sharding import validate_saved_spec
            from .durable.restore import read_generation

            got_step, manifest, arrays, _extra = read_generation(
                self.durable_dir,
                self.durable_lineage,
                step=step,
                host_rank=self.host_rank,
            )
            if got_step is None or manifest is None:
                return None
            for cat, specs in manifest.category_specs.items():
                for _path, saved_spec in specs.items():
                    validate_saved_spec(cat, saved_spec)
            restored = _restore_into_template(template, arrays)
        except Exception as e:  # noqa: BLE001 — last rung: a torn durable tier degrades to a fresh start, never a crash
            logger.warning("durable restore failed (%s); starting fresh", e)
            return None
        logger.info(
            "restored step %s from durable tier %s/%s "
            "(saved world %s, mesh %sx%s -> current mesh)",
            got_step,
            self.durable_dir,
            self.durable_lineage,
            manifest.num_hosts,
            manifest.mesh_axes,
            manifest.mesh_shape,
        )
        return got_step, restored

    # Floor for how many of each host's newest committed steps enter the
    # cross-host agreement; the effective count always exceeds the
    # configured ckpt_keep_latest (see _restore_candidate_steps) so
    # pruning can't hide a still-on-disk common step from the
    # intersection.
    RESTORE_CANDIDATE_STEPS = 8

    def _restore_candidate_steps(self) -> int:
        # Job config is uniform across hosts, so every host computes the
        # same K — required: the allgather row length depends on it.
        from ..common.config import get_context

        return max(self.RESTORE_CANDIDATE_STEPS, get_context().ckpt_keep_latest + 2)

    def _gather_restore_meta(
        self, mem_step: int, tracker_step: int, committed: List[int]
    ) -> Tuple[List[int], List[int], List[set]]:
        """Every host's (staged shm step, storage tracker step, committed
        step set) — host-only metadata, gathered before any collective
        restore. The committed set (top-K of ``storage.list_steps()``)
        rather than just the tracker: with per-host storage roots plus
        ``ckpt_keep_latest`` pruning, a host may have already deleted
        another host's tracker step while a common older step still
        exists on every host."""
        K = self._restore_candidate_steps()
        own = sorted(committed)[-K:]
        if _process_count() <= 1:
            return [mem_step], [tracker_step], [set(own)]
        from jax.experimental import multihost_utils

        row = np.full(2 + K, -1, np.int64)
        row[0], row[1] = mem_step, tracker_step
        row[2 : 2 + len(own)] = own
        gathered = multihost_utils.process_allgather(row)
        return (
            [int(v) for v in gathered[:, 0]],
            [int(v) for v in gathered[:, 1]],
            [
                {int(s) for s in host_row[2:] if s >= 0}
                for host_row in gathered
            ],
        )

    def load_consistent(self, template: Any) -> Tuple[int, Optional[Any]]:
        """``load`` + cross-host consistency (reference
        ``verify_all_rank_step_consistent`` allgather,
        flash_checkpoint/engine.py:74-95).

        ``load`` is per-host (own shm → peer → storage), so after a node
        replacement hosts can legally restore DIFFERENT steps — and a
        step-count fix alone would train a model whose shards mix two
        checkpoints.

        On a MULTI-PROCESS world the restore itself is collective: when
        the template leaves live on a global (multi-process) mesh, each
        ``device_put`` participates in cross-host transfers, so hosts
        must agree on the restore SOURCE before moving a single byte —
        a host restoring from memory while another reads storage would
        interleave mismatched collectives and deadlock/abort the world.
        The agreement therefore happens on cheap host-only metadata
        (shm meta step, storage tracker) gathered FIRST; then every
        host executes the SAME restore path:

        Drains any in-flight async stage up front (same reentrancy
        hazard as ``load``).

        - all hosts stage the same memory step → memory restore
          everywhere;
        - otherwise the NEWEST step committed on EVERY host (max of the
          intersection of per-host committed sets, capped at the newest
          tracker so a stale high-numbered step left in a reused root
          can't shadow the live history);
        - no common storage step → everyone starts fresh, consistently.
        """
        faults.inject("ckpt.engine.load", host_rank=self.host_rank)
        self._drain_stage_for_read()
        # Prefetched host read first: it already did shm attach (and
        # the peer refill for a replaced node) in the background, so
        # the agreement below runs on bytes that are ALREADY host-side.
        pre = self._consume_prefetch()
        if pre is not None:
            meta = pre[0]
        else:
            meta = self.shm.read_meta() if self.shm.attach() else None
            if meta is None and self._refill_from_peer():
                meta = self.shm.read_meta()
        mem_step = -1 if meta is None else meta.step
        storage_latest = self.storage.latest_step()
        st_step = -1 if storage_latest is None else storage_latest
        mem_steps, st_steps, committed_sets = self._gather_restore_meta(
            mem_step, st_step, self.storage.list_steps()
        )
        if mem_steps[0] >= 0 and len(set(mem_steps)) == 1:
            # only a prefetch of the AGREED step may serve the restore;
            # on an unusable image, fall through to the locked re-read —
            # the multi-process unreadable case is handled below exactly
            # as without prefetch
            if pre is not None and pre[0].step == mem_steps[0]:
                result = self._restore_from_prefetch(template, pre)
                if result is not None:
                    return result
            result = self._load_from_memory(template)
            if result is not None:
                return result
            if _process_count() > 1:
                # our shm image turned out unreadable AFTER agreement —
                # the other hosts are already inside the memory
                # restore's collectives; no safe divergence from here.
                raise RuntimeError(
                    f"agreed memory step {mem_steps[0]} unreadable "
                    "locally; restart the worker to re-rendezvous"
                )
            # single process: nothing collective at risk — storage next
        common = set.intersection(*committed_sets) if committed_sets else set()
        cap = max(st_steps)
        candidates = {s for s in common if cap < 0 or s <= cap}
        target = max(candidates) if candidates else -1
        if len(set(mem_steps)) != 1 or mem_steps[0] < 0:
            logger.info(
                "staged steps %s not uniformly restorable (trackers %s, "
                "common committed %s); restoring step %s",
                mem_steps,
                st_steps,
                sorted(common),
                target,
            )
        if target < 0:
            # Whole-pool loss: no usable shm image, peer replica, or
            # flash storage step anywhere — the durable tier is what's
            # left, under the same agree-then-restore discipline.
            return self._load_consistent_durable(template)
        return target, self._reload(template, target)

    def _durable_latest(self) -> int:
        """This host's view of the newest committed durable generation
        (-1 when the tier is off, empty, or unreachable)."""
        if not self.durable_dir:
            return -1
        try:
            from .durable.layout import DurableLayout

            latest = DurableLayout(
                self.durable_dir, self.durable_lineage
            ).latest_committed()
        except Exception as e:  # noqa: BLE001 — probe only; absence of the tier is not an error
            logger.warning("durable tier probe failed: %r", e)
            return -1
        return -1 if latest is None else latest

    def _load_consistent_durable(
        self, template: Any
    ) -> Tuple[int, Optional[Any]]:
        """Cross-host agreement for the durable rung, mirroring the
        flash rungs: gather each host's newest committed generation
        first (host-only metadata), then every host runs the SAME
        collective restore. The target is the min over hosts — the
        newest generation visible on EVERY host, robust to a shared
        filesystem propagating the newest commit unevenly."""
        own = self._durable_latest()
        if _process_count() <= 1:
            steps = [own]
        else:
            from jax.experimental import multihost_utils

            gathered = multihost_utils.process_allgather(
                np.asarray([own], np.int64)
            )
            steps = [int(v) for v in gathered[:, 0]]
        if any(s < 0 for s in steps):
            if own >= 0:
                logger.info(
                    "durable gen_%s visible locally but not on every "
                    "host (%s); starting fresh",
                    own,
                    steps,
                )
            return -1, None
        target = min(steps)
        result = self._load_from_durable(template, step=target)
        if result is None:
            if _process_count() > 1:
                raise RuntimeError(
                    f"agreed durable generation {target} unreadable "
                    "locally; restart the worker to re-rendezvous"
                )
            return -1, None
        return result

    def _reload(self, template: Any, step: int):
        result = self._load_from_storage(template, step=step)
        if result is None:
            raise RuntimeError(
                f"agreed checkpoint step {step} unreadable from storage"
            )
        return result[1]

    # -- shard topology (reference get_local/global_shard_num) -------------

    def get_local_shard_num(self) -> int:
        return 1  # one staged shard per host

    def get_global_shard_num(self) -> int:
        return self.num_hosts

    def close(self) -> None:
        """Release IPC clients and the shm mapping; in standalone mode
        also tear down the in-process saver (thread + servers), so a
        re-meshed world can build a fresh engine without leaking one
        saver stack per topology round."""
        self._prefetch_invalid = True  # cancel: skip a not-yet-started fetch
        pt = self._prefetch_thread
        if pt is not None and pt.is_alive():
            pt.join(30.0)
        self._prefetch_thread = None
        self._prefetched = None
        t = self._stage_thread
        if t is not None and t.is_alive():
            t.join(60.0)
            if t.is_alive():
                # A wedged staging thread still writes through self.shm
                # and releases through self._shard_lock: closing them
                # under it trades a leak for corruption (and the lock
                # server's death-of-holder handling will free the lock
                # when this process exits anyway). Leak loudly instead.
                logger.error(
                    "async stage still running after 60s; leaving shm/"
                    "lock open (leaked until process exit)"
                )
                return
        self.wait_staged(timeout=0.1)
        for res in (self._event_q, self._factory_q, self._shard_lock, self.shm):
            try:
                res.close()
            except Exception as e:  # noqa: BLE001 — teardown, best effort
                logger.debug("engine close: %r", e)
        if self._standalone:
            AsyncCheckpointSaver.shutdown()
