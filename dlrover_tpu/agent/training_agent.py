"""The per-host elastic training agent.

Reference: ``ElasticTrainingAgent`` (dlrover/python/elastic_agent/torch/
training.py:497) — rendezvous, worker start with retry, the monitor loop
(:999-1139) reacting to FAILED (breakpoint-save, diagnose, restart vs
relaunch) and to membership changes (restart the group to re-rendezvous),
and the KV-store exit barrier (:1333).

TPU-native shape: the "worker group" is one JAX process; a membership
change means the global device mesh is stale, so the agent tears the
process down and rebuilds the world — checkpoint-to-host-memory makes
that cheap (flash checkpoint survives worker restarts because the shm
segments live in the agent process).
"""

import os
import signal
import threading
import time
from typing import Dict, Optional

from ..attribution.recovery import (
    startup_from_process_start,
    write_startup_record,
)
from ..chaos import faults
from ..checkpoint.saver import AsyncCheckpointSaver
from ..common.constants import (
    NodeEnv,
    NodeExitReason,
    NodeStatus,
    RendezvousName,
)
from ..common.events import EventEmitter
from ..common.log import logger
from ..master.diagnosis.action import DiagnosisActionType
from ..observability import trace
from ..observability.metrics import get_registry, maybe_start_metrics_server
from ..observability.spans import startup_span
from ..rpc.client import MasterClient
from .config import ElasticLaunchConfig
from .diagnosis_agent import DiagnosisAgent, WorkerFailure
from .monitor import ResourceMonitor
from .rendezvous import (
    MasterRendezvousHandler,
    RendezvousWorld,
    reattach_world,
)
from .worker import RunResult, WorkerProcess, WorkerSpec, WorkerState

AGENT_EXIT_OK = 0
# Nonzero exit asks the platform (master/k8s) to replace this node.
AGENT_EXIT_RELAUNCH = 1
AGENT_EXIT_FATAL = 2


class ElasticTrainingAgent:
    def __init__(
        self,
        config: ElasticLaunchConfig,
        spec: Optional[WorkerSpec] = None,
        client: Optional[MasterClient] = None,
        start_ckpt_saver: bool = True,
    ):
        self._config = config
        self._client = client or MasterClient.singleton()
        self._spec = spec or WorkerSpec(
            entrypoint=config.entrypoint,
            args=config.entry_args,
            run_module=config.run_module,
            env=config.worker_env(),
            log_dir=config.log_dir,
            numa_affinity=config.numa_affinity,
        )
        self._rdzv_handler = MasterRendezvousHandler(
            RendezvousName.TRAINING,
            node_rank=config.node_rank,
            client=self._client,
            node_id=config.node_id,
            local_world_size=config.local_world_size,
            rdzv_timeout=config.rdzv_timeout,
            training_port=config.training_port,
            slice_id=config.slice_id(),
        )
        self._diagnosis = DiagnosisAgent(
            config.node_id, client=self._client, max_restarts=config.max_restarts
        )
        self._resource_monitor = ResourceMonitor(
            config.node_id, client=self._client
        )
        self._worker: Optional[WorkerProcess] = None
        self._world: Optional[RendezvousWorld] = None
        self._remaining_restarts = config.max_restarts
        self._restart_count = 0
        self._start_ckpt_saver = start_ckpt_saver
        self._stopped = threading.Event()
        self._pending_action: Optional[str] = None
        self._action_lock = threading.Lock()
        # Master-epoch fence: any RPC (heartbeat, step report, monitor
        # poll) observing a bumped epoch flags a restarted master; the
        # monitor loop then re-attaches instead of treating the blip —
        # or the re-registration joins it causes — as a world change.
        self._master_epoch_changed = threading.Event()
        if hasattr(self._client, "add_epoch_listener"):
            self._client.add_epoch_listener(self._on_master_epoch)
        self._evt = EventEmitter("agent")
        self._metric_collector = None
        self._metrics_server = None
        self._profiler_daemon = None
        self._spare = None
        # Soft-remesh handshake dir, exported to the worker (unique per
        # agent incarnation so two agents on one host never collide).
        import tempfile

        from ..trainer.remesh import REMESH_DIR_ENV

        self._remesh_dir = os.path.join(
            tempfile.gettempdir(),
            "dlrover_tpu",
            "remesh",
            f"{config.job_name}_{config.node_rank}_{os.getpid()}",
        )
        if config.soft_remesh:
            # setdefault honors a user-supplied dir (extra_env), but
            # the agent must then USE that same dir — a divergent pair
            # would silently disable the protocol. Only the
            # agent-generated default is OURS to delete wholesale; a
            # user dir may be shared (pid keying handles collisions).
            self._spec.env.setdefault(REMESH_DIR_ENV, self._remesh_dir)
            self._remesh_dir_owned = (
                self._spec.env[REMESH_DIR_ENV] == self._remesh_dir
            )
            self._remesh_dir = self._spec.env[REMESH_DIR_ENV]
        else:
            self._remesh_dir_owned = True
        self._diagnosis.register_action_handler(self._on_master_action)

    # -- lifecycle --------------------------------------------------------

    def run(self) -> int:
        # A hard-killed predecessor agent may have left its worker
        # orphaned (own session) — reap before touching shm or devices.
        from .worker import reap_stale_workers

        reap_stale_workers()
        if self._start_ckpt_saver:
            AsyncCheckpointSaver.start_async_saving_ckpt()
        self._diagnosis.start_heartbeat()
        self._resource_monitor.start()
        # Agent half of the unified metrics plane: off unless the port
        # knob is set; serves this process's registry (rendezvous/
        # restart counters, world gauges, ingested worker scrapes).
        self._metrics_server = maybe_start_metrics_server(
            "DLROVER_METRICS_AGENT_PORT"
        )
        try:
            self._setup_profiling()
            # Spawn the first spare NOW, concurrently with the
            # rendezvous: its imports race the world formation, so even
            # the FIRST worker start — including a replacement node's,
            # which is on the recovery critical path — can adopt a
            # warm interpreter.
            self._replenish_spare(delay_s=0.0)
            # everything so far (the launcher, the standalone master with
            # it, the pre-check) is the start-up phase ``agent_up``
            startup_from_process_start("agent_up")
            self._initialize_workers()
            return self._invoke_run()
        finally:
            # run() returning IS the agent stopping: the deferred
            # spare-spawn timer checks this flag, so without it a spare
            # could be spawned (and leaked) after this cleanup ran.
            self._stopped.set()
            self._diagnosis.stop()
            self._resource_monitor.stop()
            if self._metrics_server is not None:
                self._metrics_server.stop()
                self._metrics_server = None
            self._teardown_profiling()
            if self._spare is not None:
                self._spare.kill()
                self._spare = None
            if self._worker is not None:
                self._worker.stop()

    def stop(self) -> None:
        self._stopped.set()

    # -- worker management ------------------------------------------------

    def _initialize_workers(self, world=None) -> None:
        """Rendezvous (unless an already-formed ``world`` is handed in —
        a refused soft remesh consumed a round every peer is in; joining
        again would force the whole fleet through one more), then start
        the JAX process with the world's env.

        Reference training.py:883 retries initialization; a failed
        rendezvous here is fatal only after the rdzv timeout (the handler
        retries internally).
        """
        if world is not None:
            self._world = world
        else:
            # Overlapped restore: while the rendezvous below polls for
            # the new world, the saver makes this host's shm restorable
            # (refilling from the backup peer if the image is gone) so
            # the worker's restore pays no peer fetch after the join.
            AsyncCheckpointSaver.prefetch_restore_async()
            t_rdzv = time.monotonic()
            with startup_span("rdzv"), self._evt.duration(
                "rendezvous", node_rank=self._config.node_rank
            ) as span:
                self._world = self._rdzv_handler.next_rendezvous()
                span.end(
                    {
                        "round": self._world.round,
                        "rank": self._world.rank,
                        "world_size": self._world.world_size,
                    }
                )
            # MTTR phase attribution: rdzv_s is the agent's phase of
            # the recovery breakdown (attribution/recovery.py)
            rdzv_s = round(time.monotonic() - t_rdzv, 3)
        with startup_span("spawn"):
            self._start_worker()
        # One record a worker start, written at the spawn: the agent's
        # phases since its own start, or since the death it saw (a file
        # only where DLROVER_RECOVERY_DIR or --log_dir says where).
        payload = {
            "round": self._world.round,
            "restart": self._restart_count,
            "node_rank": self._config.node_rank,
            "worker_pid": self._worker.pid,
        }
        if world is None:
            payload["rdzv_s"] = rdzv_s
        write_startup_record("rdzv", payload, emitter=self._evt, close=False)

    def _start_worker(self) -> None:
        """World formed -> worker process started."""
        registry = get_registry()
        registry.counter("dlrover_agent_rendezvous_rounds_total").inc()
        registry.gauge("dlrover_agent_world_size").set(self._world.world_size)
        registry.gauge("dlrover_agent_rendezvous_round").set(self._world.round)
        logger.info(
            "world ready: round=%s rank=%s/%s coordinator=%s",
            self._world.round,
            self._world.rank,
            self._world.world_size,
            self._world.coordinator,
        )
        # A predecessor incarnation's remesh handshake files must never
        # be mistaken for the new worker's (files are pid-keyed, but a
        # recycled pid across restarts is cheap to rule out entirely).
        # The agent-generated dir is wholesale-deleted; in a
        # user-supplied (possibly shared) dir only OUR previous
        # worker's pid-keyed files are removed.
        if self._remesh_dir_owned:
            import shutil

            shutil.rmtree(self._remesh_dir, ignore_errors=True)
        else:
            # Shared dir: purge pid-keyed files whose process is GONE —
            # covers both our previous worker and a dead predecessor
            # AGENT's leftovers (a recycled pid meeting a stale ready_
            # file would get a fatal default-disposition SIGUSR1).
            try:
                entries = os.listdir(self._remesh_dir)
            except OSError:
                entries = []
            for name in entries:
                kind, _, pid_s = name.partition("_")
                if kind not in ("ready", "world", "ack") or not pid_s.isdigit():
                    continue
                try:
                    os.kill(int(pid_s), 0)
                except ProcessLookupError:
                    try:
                        os.unlink(os.path.join(self._remesh_dir, name))
                    except OSError:
                        pass
                except PermissionError:
                    pass  # alive under another uid: not ours to judge
        # Chaos hook: a delay here stretches the recovery critical path
        # (MTTR must absorb it); an error kills the agent mid-recovery
        # (the master's relaunch budget takes over).
        faults.inject(
            "agent.worker_start",
            node_rank=self._config.node_rank,
            restart=self._restart_count,
        )
        self._worker = WorkerProcess(self._spec, restart_count=self._restart_count)
        spare = self._take_spare()
        how = self._worker.start(
            dynamic_env=self._world_env(self._world), spare=spare
        )
        if how != "warm" and spare is not None:
            if spare.proc.poll() is None:
                # not adopted (imports still racing): keep for next time
                self._spare = spare
            else:
                spare.kill()  # died during imports: release log fd/marker
        self._replenish_spare()
        self._resource_monitor.watch_pid(self._worker.pid)
        self._report_status(NodeStatus.RUNNING)

    def _world_env(self, world: RendezvousWorld) -> Dict[str, str]:
        """The dynamic (per-rendezvous-round) part of the env contract.

        Includes the trace contract (DLROVER_TRACE_ID/_PARENT_SPAN) when
        an incident is active, so the worker spawned BY a recovery joins
        the incident's timeline; both start paths (cold spawn and
        warm-spare hand-off) carry dynamic_env, so both inherit it.
        """
        env = {
            NodeEnv.COORDINATOR_ADDRESS: world.coordinator,
            NodeEnv.NUM_PROCESSES: str(world.world_size),
            NodeEnv.PROCESS_ID: str(world.rank),
            NodeEnv.NODE_RANK: str(self._config.node_rank),
            NodeEnv.NODE_NUM: str(world.world_size),
        }
        env.update(trace.child_env())
        return env

    def _begin_incident(self, kind: str, **content) -> None:
        """Open a new incident trace at a detection point: every event
        this process emits from here on — and, via the RPC and spawn
        contracts, the master's handler-side events and the replacement
        worker's — shares one trace_id until the next incident."""
        ctx = trace.start_incident()
        get_registry().counter("dlrover_agent_incidents_total").inc()
        self._evt.instant("incident_detected", kind=kind, **content)
        logger.info("incident %s opened (trace %s)", kind, ctx.trace_id)

    # -- warm-spare pool (one pre-imported interpreter per agent) ---------

    def _take_spare(self):
        spare, self._spare = self._spare, None
        return spare

    # Spare spawn is DEFERRED off the recovery critical path: paying
    # the spare's import tax while the fresh worker is itself booting
    # doubles the CPU demand at exactly the moment MTTR is measured.
    SPARE_SPAWN_DELAY_S = 8.0

    def _replenish_spare(self, delay_s: Optional[float] = None) -> None:
        """Keep exactly one warm spare on deck (spawned after a delay,
        except at agent startup where the spare's imports race the
        rendezvous instead of a live worker's recovery)."""
        if not self._config.warm_spare or self._spare is not None:
            return

        def spawn():
            if self._spare is not None or self._stopped.is_set():
                return
            from .worker import WarmSpare

            try:
                self._spare = WarmSpare(self._spec)
            except Exception as e:  # noqa: BLE001 — an optimization only
                logger.warning("warm spare spawn failed: %s", e)
                self._spare = None

        if delay_s is None:
            delay_s = self.SPARE_SPAWN_DELAY_S
        if delay_s <= 0:
            spawn()
            return
        timer = threading.Timer(delay_s, spawn)
        timer.daemon = True
        timer.start()

    # -- soft re-mesh (survivors keep their process) ----------------------

    def _try_soft_remesh(self):
        """Offer the new world to the live worker (trainer/remesh.py).

        The rendezvous for the NEW round runs while the worker keeps
        training — the restart-path ordering (stop, then rendezvous)
        inverted, which is the whole win: a node replacement costs
        survivors zero downtime.

        Returns ``(outcome, world)``: "adopted" (nobody died),
        "worker_exited" (let the monitor loop's normal poll handling
        run — a crash must go through diagnosis/budgets, a success
        through the exit barrier), or "restart" with the
        already-formed world (when one exists) so the hard path can
        reuse the round instead of forcing every peer through another.
        """
        import json as _json

        if not self._config.soft_remesh or self._worker is None:
            return "restart", None
        pid = self._worker.pid
        ready = os.path.join(self._remesh_dir, f"ready_{pid}")
        if not pid or not os.path.exists(ready):
            return "restart", None  # worker doesn't speak the protocol
        with self._evt.duration(
            "soft_remesh", node_rank=self._config.node_rank
        ) as span:
            world = self._rdzv_handler.next_rendezvous()
            ack_path = os.path.join(self._remesh_dir, f"ack_{pid}")
            try:
                os.unlink(ack_path)
            except OSError:
                pass
            contract = {
                "coordinator": world.coordinator,
                "num_processes": world.world_size,
                "process_id": world.rank,
                "node_rank": self._config.node_rank,
                "round": world.round,
            }
            with open(
                os.path.join(self._remesh_dir, f"world_{pid}"), "w"
            ) as f:
                _json.dump(contract, f)
            try:
                os.kill(pid, signal.SIGUSR1)
            except ProcessLookupError:
                return "worker_exited", world
            except PermissionError:
                # worker ALIVE but unsignalable (privilege boundary):
                # only a restart can deliver the new world
                return "restart", world
            deadline = time.time() + self._config.soft_remesh_timeout_s
            while time.time() < deadline:
                if self._worker.poll().state != WorkerState.RUNNING:
                    span.end({"outcome": "worker_exited"})
                    return "worker_exited", world
                try:
                    with open(ack_path) as f:
                        accepted = bool(_json.load(f).get("accepted"))
                    break
                except (OSError, ValueError):
                    time.sleep(0.2)
            else:
                logger.warning(
                    "soft remesh: worker %s never acked; restarting", pid
                )
                span.end({"outcome": "timeout"})
                return "restart", world
            span.end({"outcome": "accepted" if accepted else "refused"})
        if not accepted:
            return "restart", world
        self._world = world
        logger.info(
            "soft remesh: round=%s adopted by live worker %s "
            "(rank %s/%s, zero survivor downtime)",
            world.round,
            pid,
            world.rank,
            world.world_size,
        )
        self._report_status(NodeStatus.RUNNING)
        return "adopted", world

    def _restart_workers(self, reason: str, world=None) -> None:
        logger.info("restarting worker (%s)", reason)
        get_registry().counter("dlrover_agent_worker_restarts_total").inc()
        self._evt.instant("restart_worker", reason=reason)
        if self._worker is not None:
            with startup_span("worker_stop"):
                self._worker.stop()
        self._restart_count += 1
        self._initialize_workers(world=world)

    # -- monitor loop -----------------------------------------------------

    def _invoke_run(self) -> int:
        while not self._stopped.is_set():
            time.sleep(self._config.monitor_interval)
            # Chaos hook: wedging the supervision loop simulates a hung
            # agent — the master's heartbeat deadline must catch it.
            faults.inject(
                "agent.monitor_poll", node_rank=self._config.node_rank
            )
            action = self._take_pending_action()
            if action is not None:
                code = self._apply_master_action(action)
                if code is not None:
                    return code
                continue
            result = self._worker.poll()
            if result.state == WorkerState.SUCCEEDED:
                self._report_status(NodeStatus.SUCCEEDED)
                self._exit_barrier()
                return AGENT_EXIT_OK
            if result.state == WorkerState.FAILED:
                code = self._handle_worker_failure(result)
                if code is not None:
                    return code
                continue
            changed = self._membership_changed()
            # The epoch check runs AFTER the membership poll on purpose:
            # that poll's own response may be the first to carry the new
            # epoch, and a restarted master's re-registering peers read
            # as waiters — re-attach must own that signal, not the
            # restart path.
            if self._master_epoch_changed.is_set():
                self._master_epoch_changed.clear()
                self._reattach_master()
                continue
            if changed:
                self._begin_incident(
                    "membership_change", node_rank=self._config.node_rank
                )
                outcome, world = self._try_soft_remesh()
                if outcome == "worker_exited":
                    continue  # normal poll handling owns exits/failures
                if outcome != "adopted":
                    # reuse an already-formed round (refusal/timeout
                    # happened AFTER the rendezvous): restarting into it
                    # spares every peer a second global round
                    self._restart_workers("membership changed", world=world)
        return AGENT_EXIT_OK

    # -- master crash re-attach (epoch fence) -----------------------------

    def _on_master_epoch(self, old_epoch: int, new_epoch: int) -> None:
        logger.warning(
            "master epoch %s -> %s: restarted master; scheduling re-attach",
            old_epoch,
            new_epoch,
        )
        self._master_epoch_changed.set()

    def _reattach_master(self) -> None:
        """A restarted master replayed its journal: re-register this node
        and verify the recovered world. When the replayed world matches
        the cached one the live JAX worker keeps training — the master
        crash costs seconds of coordination, zero worker restarts."""
        self._begin_incident(
            "master_restart", node_rank=self._config.node_rank
        )
        t0 = time.monotonic()
        with self._evt.duration(
            "master_reattach", node_rank=self._config.node_rank
        ) as span:
            # Re-register first: the replayed node table is re-asserted
            # even if the journal was lost (update_node_status creates
            # the node when missing).
            self._report_status(NodeStatus.RUNNING)
            outcome, world = reattach_world(self._rdzv_handler, self._world)
            span.end({"outcome": outcome})
        from ..attribution.recovery import record_phase_file

        record_phase_file(
            "reattach",
            {
                "reattach_s": round(time.monotonic() - t0, 3),
                "outcome": outcome,
                "node_rank": self._config.node_rank,
            },
        )
        if outcome == "intact":
            logger.info(
                "master re-attach: recovered world intact (rank %s/%s); "
                "worker untouched",
                self._world.rank if self._world else -1,
                self._world.world_size if self._world else 0,
            )
            return
        if outcome == "matched":
            self._world = world
            logger.info(
                "master re-attach: re-formed world matches the cached one "
                "(round %s); worker untouched",
                world.round,
            )
            return
        self._restart_workers("master restarted with changed world", world=world)

    def _handle_worker_failure(self, result: RunResult) -> Optional[int]:
        """Breakpoint-save, diagnose, restart or relaunch (training.py:1074)."""
        # death seen -> respawn decided: the first phase of a restart's record
        with startup_span("respawn_decide"):
            logger.error(
                "worker failed rc=%s signal=%s restart=%s",
                result.returncode,
                result.signal,
                self._restart_count,
            )
            self._begin_incident(
                "worker_failure",
                returncode=result.returncode,
                signal=result.signal,
                node_rank=self._config.node_rank,
            )
            if self._config.save_at_breakpoint:
                self._save_ckpt_at_breakpoint()
            failure = WorkerFailure(
                node_rank=self._config.node_rank,
                restart_count=self._restart_count,
                returncode=result.returncode,
                signal=result.signal,
                log_tail=self._worker.tail_log(),
            )
            self._diagnosis.report_failure(failure)
            action = self._diagnosis.diagnose_training_failure(failure)
        if (
            action == DiagnosisActionType.RESTART_WORKER
            and self._remaining_restarts > 0
        ):
            self._remaining_restarts -= 1
            self._restart_workers("worker failure")
            return None
        # RELAUNCH_REQUESTED, not FATAL_ERROR: this exit path IS the
        # agent asking the master for a replacement node. FATAL_ERROR is
        # the one reason should_relaunch() never honors, so reporting it
        # here stranded the node forever (storm-observed: the job kept
        # training one host short with budget to spare).
        self._report_status(
            NodeStatus.FAILED, exit_reason=NodeExitReason.RELAUNCH_REQUESTED
        )
        logger.error("worker failure unrecoverable on this node; relaunching")
        return AGENT_EXIT_RELAUNCH

    def _membership_changed(self) -> bool:
        """True when the master has waiters that require a new world.

        The master applies the node-unit rules (rdzv_manager: waiters
        trigger a restart only when ≥ node_unit or a previous member
        re-joined), so the agent only asks the count.
        """
        try:
            return self._rdzv_handler.num_nodes_waiting() > 0
        except Exception as e:
            logger.warning("num_nodes_waiting failed: %s", e)
            return False

    # -- native profiling (default-on product path) ------------------------

    def _setup_profiling(self) -> None:
        """Make profiling passive and automatic (reference: xpu_timer is
        preloaded into every trainer by ``xpu_timer_launch`` and the
        agent auto-registers the collector, diagnosis_agent.py:85).

        Worker side: the interposer env goes into the worker spec so the
        trainer's jax loads it at backend init — zero user code. Agent
        side: the metric collector scrapes the worker's native /metrics
        (incl. the stall verdict the master's hang check consumes) and
        rank 0 serves the cluster-wide profiler daemon.
        """
        if not self._config.profile_enabled():
            return
        try:
            from ..profiler.pjrt import prepare_worker_profiling_env

            env = prepare_worker_profiling_env(
                port=self._config.profiler_port
            )
            if env is None:
                return  # reason already logged; never blocks training
            self._spec.env.update(env)
            port = int(env["DLROVER_TT_PORT"])
            from .metric_collector import ProfilerMetricCollector

            self._metric_collector = ProfilerMetricCollector(
                port,
                client=self._client,
                interval_s=self._config.profiler_scrape_interval_s,
            )
            self._metric_collector.start()
            logger.info("native profiling on: worker tt port %s", port)
        except Exception as e:  # noqa: BLE001 — never blocks training
            logger.warning("profiling setup failed: %s", e)
            self._metric_collector = None
            return
        if self._config.node_rank == 0:
            try:
                from ..profiler.daemon import ProfilerDaemon

                self._profiler_daemon = ProfilerDaemon(
                    client=self._client,
                    port=self._config.profiler_daemon_port,
                )
                self._profiler_daemon.start()
            except Exception as e:  # noqa: BLE001 — aux service only
                logger.warning("profiler daemon failed to start: %s", e)
                self._profiler_daemon = None

    def _teardown_profiling(self) -> None:
        if self._metric_collector is not None:
            self._metric_collector.stop()
            self._metric_collector = None
        if self._profiler_daemon is not None:
            self._profiler_daemon.stop()
            self._profiler_daemon = None

    # -- master-issued actions -------------------------------------------

    def _on_master_action(self, action_type: str, config: dict) -> None:
        if action_type == DiagnosisActionType.STACK_DUMP:
            # Executed inline (not queued): the whole point is capturing
            # the wedged state BEFORE any restart action tears it down.
            self._dump_worker_stacks(config.get("reason", ""))
            return
        with self._action_lock:
            self._pending_action = action_type

    def _dump_worker_stacks(self, reason: str) -> None:
        """Signal the worker for a faulthandler traceback and ship it to
        the master (reference all-rank stack dump, manager.cc:393-414)."""
        from ..profiler.stack_dump import trigger_and_read

        pid = self._worker.pid if self._worker is not None else None
        if not pid:
            return
        text = trigger_and_read(pid)
        if not text:
            logger.warning("worker %s produced no stack dump", pid)
            return
        logger.info(
            "worker stack dump (%s):\n%s", reason or "requested", text
        )
        # Profiled workers also dump their trace ring — the device-side
        # half of the post-mortem (what the chip was doing next to what
        # the host was doing). The binary lands on the host; the event
        # carries its path for the timeline merge tools.
        ring_path = None
        if self._metric_collector is not None:
            try:
                from ..profiler.stack_dump import request_ring_dump

                ring_path = request_ring_dump()
                if ring_path:
                    logger.info("worker trace ring dumped: %s", ring_path)
            except Exception as e:  # noqa: BLE001 — aux only
                logger.warning("ring dump request failed: %s", e)
        try:
            self._client.report_event(
                event_type="stack_dump",
                instance=f"node-{self._config.node_id}",
                action=reason or "requested",
                msg=(f"[ring:{ring_path}]\n" if ring_path else "")
                + text[-8000:],
            )
        except Exception:
            logger.warning("stack dump report to master failed")

    def _take_pending_action(self) -> Optional[str]:
        with self._action_lock:
            action, self._pending_action = self._pending_action, None
            return action

    def _apply_master_action(self, action: str) -> Optional[int]:
        if action == DiagnosisActionType.RESTART_WORKER:
            self._restart_workers("master-issued restart")
            return None
        if action == DiagnosisActionType.RELAUNCH_WORKER:
            self._worker.stop()
            self._report_status(NodeStatus.FAILED, exit_reason="relaunched")
            return AGENT_EXIT_RELAUNCH
        if action == DiagnosisActionType.JOB_ABORTION:
            self._worker.stop()
            self._report_status(NodeStatus.FAILED, exit_reason="job_aborted")
            return AGENT_EXIT_FATAL
        return None

    # -- helpers ----------------------------------------------------------

    def _save_ckpt_at_breakpoint(self) -> None:
        """Persist whatever step is staged in shm before teardown
        (reference training.py:1216 → ckpt_saver.py:758)."""
        saver = AsyncCheckpointSaver._instance
        if saver is None:
            return
        try:
            if saver.save_shm_to_storage():
                logger.info("breakpoint checkpoint persisted")
        except Exception as e:
            logger.warning("breakpoint save failed: %s", e)

    def _report_status(
        self, status: str, exit_reason: str = ""
    ) -> None:
        try:
            self._client.report_node_status(
                status, exit_reason=exit_reason, restart_count=self._restart_count
            )
        except Exception as e:
            logger.warning("status report failed: %s", e)

    def _exit_barrier(self, timeout: float = 300.0) -> None:
        """All agents wait here so stragglers can finish persisting
        checkpoints before the job object is torn down (training.py:1333)."""
        if self._world is None or self._world.world_size <= 1:
            return
        key = f"exit_barrier/{self._world.round}"
        try:
            count = self._client.kv_store_add(key, 1)
            deadline = time.time() + timeout
            while count < self._world.world_size and time.time() < deadline:
                time.sleep(0.5)
                count = self._client.kv_store_add(key, 0)
        except Exception as e:
            logger.warning("exit barrier failed: %s", e)
