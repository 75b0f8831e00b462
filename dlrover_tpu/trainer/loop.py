"""ElasticTrainLoop: the convenience training loop for elastic jobs.

Reference: ``ElasticTrainer`` (``dlrover/trainer/torch/elastic/
trainer.py:181``) — the L7 wrapper users reach for: fixed global batch
via world-size-aware gradient accumulation, checkpoint cadence, resume,
and step reporting, so a training script is the model + data and nothing
else. The TPU shape: consistent resume through
``CheckpointEngine.load_consistent``, staged-memory saves every step,
async storage saves on a cadence, and master step reports feeding the
PerfMonitor/goodput/hang machinery.
"""

import os
import threading
import time
from typing import Any, Callable, Iterable, Optional, Tuple

# Imported at module load on purpose: _write_recovery_record runs right
# after the first step, and a package import at that point means
# dataclass machinery + a GC burst in the middle of live training — the
# exact moment a worker can least afford allocator churn.
from ..attribution.recovery import write_startup_record
from ..common.compile_cache import compile_seconds_since
from ..common.constants import NodeEnv
from ..common.events import EventEmitter
from ..common.log import logger
from ..observability.metrics import get_registry
from ..observability.spans import process_accumulator, span, startup_span

# Process-wide GC tracer installed by the first loop run (gc.callbacks
# hooks must not stack when run() is called repeatedly).
_gc_tracer = None


def gradient_accumulation_steps(max_workers: int, current_workers: int) -> int:
    """Accumulation factor keeping the global batch fixed as the world
    shrinks (reference trainer.py:196-202): with max 8 workers and 2
    alive, each does 4 accumulation slices per optimizer step."""
    if current_workers <= 0 or max_workers <= current_workers:
        return 1
    if max_workers % current_workers:
        # non-divisible worlds round UP: global batch grows slightly
        # rather than silently shrinking
        return -(-max_workers // current_workers)
    return max_workers // current_workers


def _release_replaced(template: Any, restored: Any) -> None:
    """Free the device buffers of the template state a restore replaced.

    The caller of :meth:`ElasticTrainLoop.run` still holds the state it
    passed in, so after a restore the device carries the state TWICE
    until that frame returns — and at a real size the step then cannot
    be loaded (GPT-2-small, b32 on a 16 GB chip: 13.25 G to reserve,
    12.97 G free). The template is dead either way: the step donates its
    state, so the caller's reference is invalid after the first step on
    the non-resume path too. Leaves the restore kept (same object) stay.
    """
    import jax

    kept = {id(leaf) for leaf in jax.tree_util.tree_leaves(restored)}
    for leaf in jax.tree_util.tree_leaves(template):
        if (
            isinstance(leaf, jax.Array)
            and id(leaf) not in kept
            and not leaf.is_deleted()
        ):
            leaf.delete()


class ElasticTrainLoop:
    """Drives ``step_fn`` with elastic resume + checkpoint cadence.

    >>> loop = ElasticTrainLoop(engine, step_fn, ctx=elastic_context(),
    ...                         max_steps=10_000, storage_every=200)
    >>> state = loop.run(state, data_iter)

    ``step_fn(state, *batch) -> (state, loss)``; ``data_iter`` yields
    batch tuples. The loop:
    - restores via ``load_consistent`` (cross-host step agreement),
    - stages every step to shm, persists every ``storage_every`` steps
      (0 disables disk persistence — shm staging only),
    - reports steps to the master (PerfMonitor / goodput / hang check),
    - stops at ``max_steps`` and waits for pending persists.
    """

    def __init__(
        self,
        engine,
        step_fn: Callable,
        ctx=None,
        max_steps: int = 0,
        memory_every: int = 1,
        storage_every: int = 100,
        log_every: int = 10,
        on_step: Optional[Callable[[int, float], None]] = None,
        device_monitor: bool = True,
        trace_host: bool = True,
        soft_remesh: bool = True,
        on_remesh: Optional[Callable] = None,
        prefetch_input: Optional[bool] = None,
        input_stage_fn: Optional[Callable[[Tuple], Tuple]] = None,
        compile_ahead=None,
        replanner=None,
        on_replan: Optional[Callable] = None,
    ):
        self.engine = engine
        self.step_fn = step_fn
        self.ctx = ctx
        self.max_steps = max_steps
        self.memory_every = max(1, memory_every)
        # 0 disables storage persistence entirely (shm staging only):
        # in-process multi-tenant rigs share one agent saver, and a
        # second engine's queued disk save can starve behind the
        # first's event loop — a loop that never persists must not
        # block its exit-path wait_saving on it either
        self.storage_every = max(0, storage_every)
        self.log_every = max(1, log_every)
        self.on_step = on_step
        self.start_step = 0
        # Per-device HBM/duty-cycle reporter — runs HERE because only
        # the trainer's PJRT client can see TPU memory stats (see
        # trainer/device_monitor.py). Needs a master to report to.
        self._device_monitor = None
        if device_monitor and ctx is not None and ctx.client is not None:
            from .device_monitor import DeviceMonitor

            self._device_monitor = DeviceMonitor(client=ctx.client)
        self._trace_host = trace_host
        # Soft re-mesh: adopt a shape-compatible new world at a step
        # boundary instead of dying (see trainer/remesh.py). Survivors
        # of a node replacement keep training THROUGH the rendezvous.
        self._remesh = None
        if soft_remesh and ctx is not None:
            from .remesh import SoftRemesh

            candidate = SoftRemesh(ctx, on_remesh=on_remesh)
            if candidate.available:
                self._remesh = candidate
        # Double-buffered input (trainer/dataloader.py PrefetchIterator):
        # the next batch (and its optional h2d staging via
        # ``input_stage_fn``, e.g. make_global_array) is pulled one step
        # ahead on a background thread. None defers to the Context knob
        # (DLROVER_INPUT_PREFETCH); pass False (--sync-input) for
        # sources that must not observe a draw ahead of the step.
        self._prefetch_input = prefetch_input
        self._input_stage_fn = input_stage_fn
        # Compile-ahead remesh (trainer/precompile.py): a
        # CompileAheadService (or a bare ``build_fn(world)`` the loop
        # wraps in one) that AOT-compiles anticipated world sizes into
        # the persistent compile cache while this world trains. Started
        # only after the first step — it must not race the live
        # compile for the CPU.
        self._compile_ahead = compile_ahead
        self._compile_svc = None
        # Elastic hybrid replanning (parallel/replan.py,
        # docs/elastic_parallelism.md): after an adopted soft re-mesh
        # the replanner picks the best DP×TP×PP rung for the new device
        # count and ``on_replan(plan, state)`` executes the trade —
        # rebuild mesh + step_fn for the rung, drive the staged flash
        # image through RESHARD_RULES (engine.load_resharded), and
        # return ``(step_fn, state)``. None keeps the pre-rung
        # accum-only behavior.
        self._replanner = replanner
        self._on_replan = on_replan
        # measured step-time feed for the replanner's cost model,
        # sampled at log cadence (no extra host syncs on the hot path)
        self._last_log_t: Optional[float] = None
        self._last_log_step = 0
        # MTTR phase attribution (attribution/recovery.py): wall time of
        # the phases this process owns, spooled to DLROVER_RECOVERY_DIR.
        self.last_restore_s = 0.0
        self.last_first_step_s = 0.0
        # measured (common/compile_cache.py: JAX's own events): seconds of
        # tracing, lowering and compile or cache read inside the first step
        self.last_compile_s: Optional[float] = None
        self._recovery_written = False
        # Cooperative step-boundary stop (chip-pool revocation,
        # operator pause): run() breaks at the NEXT boundary and walks
        # its normal tail — the final state is staged to shm with
        # retries, pending persists drain — so the returned state is
        # flash-checkpoint-backed and a successor (smaller world, new
        # accumulation factor) resumes exactly where this run stopped.
        # One-shot per loop instance: construct a fresh loop (the
        # repo-wide pattern) rather than re-running a stopped one.
        self._stop_requested = threading.Event()
        # Incident-timeline milestones (observability/trace_merge.py):
        # train_restore spans the checkpoint reload, train_resume marks
        # steady state — the reshard and resume anchors of the merged
        # phase breakdown. Inherits DLROVER_TRACE_ID from the agent.
        self._evt = EventEmitter("trainer")

    def request_stop(self) -> None:
        """Ask a running :meth:`run` to stop at the next step boundary
        (thread-safe; callable from any thread). The loop stages the
        final step before returning, so the stop is handoff-grade."""
        self._stop_requested.set()

    @property
    def stop_requested(self) -> bool:
        return self._stop_requested.is_set()

    def restore(self, state: Any) -> Tuple[int, Any]:
        """(start_step, state) — consistent across hosts.

        ``load_consistent`` walks the full fallback chain: own shm →
        peer replica → per-job storage → the durable tier
        (``DLROVER_DURABLE_DIR``, reshard-on-read — survives losing
        every host of the pool). Each rung agrees cross-host on the
        source before any collective placement runs.
        """
        t0 = time.monotonic()
        with startup_span("restore"), self._evt.duration(
            "train_restore"
        ) as span:
            loaded, restored = self.engine.load_consistent(state)
            span.end({"loaded_step": loaded})
        self.last_restore_s = time.monotonic() - t0
        if loaded >= 0 and restored is not None:
            logger.info(
                "resuming from step %s (restore %.2fs)",
                loaded,
                self.last_restore_s,
            )
            self.start_step = loaded + 1
            _release_replaced(state, restored)
            return self.start_step, restored
        self.start_step = 0
        return 0, state

    def run(
        self,
        state: Any,
        data_iter: Optional[Iterable[Tuple]] = None,
        data_factory: Optional[Callable[[int], Iterable[Tuple]]] = None,
    ) -> Any:
        """Train until ``max_steps`` (or data exhaustion).

        Data resume: pass ``data_factory`` — called with the resumed
        start step AFTER the checkpoint restore — to build an iterator
        positioned at the right sample (e.g. an
        ``ElasticDistributedSampler`` with ``consumed_samples`` set). A
        plain ``data_iter`` is only correct for stateless/randomized
        sources: a sequential dataset would replay its FIRST batches
        after a resume.
        """
        start, state = self.restore(state)
        if data_factory is not None:
            data_iter = data_factory(start)
        if data_iter is None:
            raise ValueError("run() needs data_iter or data_factory")
        if self._trace_host:
            # on the RAW iterator: draw timings must cover the real
            # source even when the prefetch thread does the drawing
            self._install_host_tracer(data_iter)
        prefetch = self._prefetch_input
        if prefetch is None:
            from ..common.config import get_context

            prefetch = get_context().input_prefetch
        prefetcher = None
        if prefetch:
            from .dataloader import PrefetchIterator

            data_iter = prefetcher = PrefetchIterator(
                data_iter, stage_fn=self._input_stage_fn
            )
        elif self._input_stage_fn is not None:
            # sync escape hatch still applies the staging, inline
            stage = self._input_stage_fn
            data_iter = (stage(batch) for batch in data_iter)
        if self._remesh is not None:
            self._remesh.install()
        if self._device_monitor is not None:
            self._device_monitor.start()
        try:
            return self._run_inner(state, data_iter, start)
        finally:
            if prefetcher is not None:
                prefetcher.close()
            if self._compile_svc is not None:
                self._compile_svc.stop()
            if self._remesh is not None:
                self._remesh.uninstall()
            # stop() even when step_fn raises: a leaked daemon reporter
            # would keep shipping stale gauges for the process life and
            # block a retried run() from restarting it cleanly.
            if self._device_monitor is not None:
                self._device_monitor.stop()

    def _install_host_tracer(self, data_iter) -> None:
        """Slow-dataloader visibility with zero user annotations: the
        data iterator (and any DLROVER_PY_TRACE_TARGETS functions) get
        per-call timings in the native profiler stream — the reference's
        py_tracing.c capability (SURVEY §2.15), via sys.monitoring so
        untraced code carries no instrumentation at all."""
        try:
            from ..profiler.host_stalls import GcStallTracer
            from ..profiler.py_tracer import (
                FunctionTracer,
                install_crash_hook,
            )

            tracer = FunctionTracer.singleton()
            tracer.add_iterator(data_iter)
            tracer.add_env_targets()
            tracer.install()
            install_crash_hook(tracer.timer)
            # GC pauses in the same stream (a straggler whose cause is
            # gen-2 GC is attributable at a glance) — hooks fire only
            # at collections, so always-on costs nothing between them.
            # One per PROCESS: repeated loop runs must not stack hooks.
            global _gc_tracer
            if _gc_tracer is None:
                _gc_tracer = GcStallTracer(tracer.timer).install()
        except Exception as e:  # noqa: BLE001 — aux, never blocks training
            logger.warning("host tracer unavailable: %s", e)

    # -- warm-restart instrumentation --------------------------------------

    def _record_boot_step(self, loss, t0: float) -> None:
        """Time the first step after (re)start, call to result ready: it
        carries the XLA (re)compile, or the cache read the persistent
        compile cache (and compile-ahead) turns it into. ``compile_s`` is
        what JAX's own events measured of the programs built inside it.
        Blocks on the loss so the measurement covers execution, not just
        dispatch — paid on exactly one step."""
        try:
            import jax

            jax.block_until_ready(loss)
        # tpulint: ignore[exception-swallow] a non-jax step output lands here once a start; the timing fallback is the designed behavior
        except Exception:  # noqa: BLE001 — non-jax step_fn outputs
            pass
        dt = time.monotonic() - t0
        began_ns = time.time_ns() - int(dt * 1e9)
        self.last_first_step_s = dt
        self.last_compile_s = compile_seconds_since(began_ns)
        process_accumulator().add_startup_phase("first_step", began_ns, dt)
        # Start anticipating only now: the service must never
        # compete with the live first compile for the CPU.
        self._start_compile_ahead()
        # The watermark moves again: the incident (if any) is over.
        self._evt.instant(
            "train_resume",
            restore_s=round(self.last_restore_s, 3),
            first_step_s=round(self.last_first_step_s, 3),
            compile_s=round(self.last_compile_s, 3),
        )
        self._write_recovery_record()

    def _anticipation_current(self) -> int:
        """The "current world" the compile-ahead ladder pivots on:
        process count on the 1D accum ladder, DEVICE count when the
        replanner's 2D rung ladder drives anticipation (rungs factor
        devices, not hosts)."""
        if self._replanner is not None and self.ctx is not None:
            return self.ctx.world_device_count()
        return self.ctx.num_processes if self.ctx is not None else 1

    def _start_compile_ahead(self) -> None:
        ca = self._compile_ahead
        if ca is None:
            return
        if self._compile_svc is not None:
            # a retried run() stopped the service in its finally;
            # start() clears the stop flag and respawns the thread
            self._compile_svc.start()
            return
        try:
            from .precompile import CompileAheadService

            if isinstance(ca, CompileAheadService):
                svc = ca
            else:
                current = (
                    self.ctx.num_processes if self.ctx is not None else 1
                )
                node_unit = int(
                    os.environ.get(NodeEnv.NODE_UNIT, "1") or 1
                )
                # MAX_NODES is the static job ceiling; NODE_NUM is
                # clobbered to the CURRENT world each rendezvous round,
                # so reading it here would hide every grow world and
                # skew the shrink ladder's accumulation factors.
                max_workers = max(
                    current,
                    int(os.environ.get(NodeEnv.MAX_NODES, "0") or 0),
                )
                if self._replanner is not None:
                    # 2D ladder: scale the host-denominated knobs to
                    # devices (the planner's unit).
                    per_host = max(
                        1, self._anticipation_current() // max(1, current)
                    )
                    current *= per_host
                    node_unit *= per_host
                    max_workers *= per_host
                svc = CompileAheadService(
                    ca,
                    current_world=current,
                    max_workers=max_workers,
                    node_unit=node_unit,
                    planner=self._replanner,
                )
            self._compile_svc = svc.start()
        except Exception as e:  # noqa: BLE001 — an optimization only
            logger.warning("compile-ahead unavailable: %s", e)

    def _apply_replan(self, state):
        """Execute a DP↔PP/TP trade at the adopted-remesh boundary.

        The replanner scores the rung ladder for the new device count;
        when the winner changes mesh extents, ``on_replan(plan, state)``
        performs the live transition — rebuild mesh/step program for
        the rung (compile-ahead made this a cache read) and drive the
        staged flash image through RESHARD_RULES via
        ``engine.load_resharded`` — returning ``(step_fn, state)``.
        Every failure path keeps the current program: accum-only
        continuation is always correct, just slower.
        """
        try:
            n = self._anticipation_current()
            plan = self._replanner.plan(n)
        except Exception as e:  # noqa: BLE001 — incl. injected faults
            logger.warning("replan failed (%s); keeping current program", e)
            return state
        if plan.rung == self._replanner.current:
            return state
        if self._on_replan is None:
            logger.info(
                "replan chose %s but no on_replan executor; keeping "
                "current program",
                plan.rung.label(),
            )
            return state
        with self._evt.duration(
            "live_reshard",
            from_rung=plan.current.label(),
            to_rung=plan.rung.label(),
            accum=plan.rung.accum,
        ) as span:
            try:
                result = self._on_replan(plan, state)
            except Exception as e:  # noqa: BLE001 — keep training
                logger.warning(
                    "live reshard %s → %s failed (%s); keeping current "
                    "program",
                    plan.current.label(),
                    plan.rung.label(),
                    e,
                )
                span.fail(repr(e))
                return state
            applied = result is not None
            if applied:
                new_step_fn, state = result
                if new_step_fn is not None:
                    self.step_fn = new_step_fn
                self._replanner.adopt(plan.rung)
            span.end(
                {
                    "applied": applied,
                    "hybrid_vs_accum_goodput_x": round(
                        plan.hybrid_vs_accum_goodput_x, 4
                    ),
                }
            )
        return state

    def _write_recovery_record(self) -> None:
        """This start's record, once: the recovery breakdown's keys and
        the whole start-up beside them (attribution/recovery.py), to the
        spool (no file without DLROVER_RECOVERY_DIR), the event stream
        and the log."""
        if self._recovery_written:
            return
        self._recovery_written = True
        payload = {
            "resumed": self.start_step > 0,
            "restart": int(os.environ.get(NodeEnv.RESTART_COUNT, "0") or 0),
            "restore_s": round(self.last_restore_s, 3),
            "first_step_s": round(self.last_first_step_s, 3),
        }
        if self.last_compile_s is not None:
            payload["compile_s"] = round(self.last_compile_s, 3)
        write_startup_record("worker", payload, emitter=self._evt)

    # tpulint: hotpath — scalar fetch at log cadence only
    def _report(self, step, loss) -> None:
        """What the loop tells the outside after a step: the agent's
        progress report, the caller's ``on_step``, the log line."""
        if self.ctx is not None:
            self.ctx.report_step(step)
        if self.on_step is not None:
            self.on_step(step, loss)
        if step % self.log_every == 0:
            # scalar fetch only when logging: a per-step float()
            # would serialize host and device
            # tpulint: ignore[host-sync] log-cadence scalar fetch,
            # amortized over log_every steps by design
            logger.info("step %s: loss %.4f", step, float(loss))
            # registry gauges at log cadence only — the hot path
            # stays free of lock traffic between log points
            get_registry().gauge("dlrover_trainer_last_step").set(step)
            if self._replanner is not None:
                # the float(loss) above already synced, so the wall
                # clock here brackets fully-executed steps — feed
                # the measured per-step time into the cost model
                now = time.monotonic()
                if (
                    self._last_log_t is not None
                    and step > self._last_log_step
                ):
                    self._replanner.observe_step_time(
                        (now - self._last_log_t)
                        / (step - self._last_log_step)
                    )
                self._last_log_t = now
                self._last_log_step = step

    # tpulint: hotpath — the per-step path; scalar fetches only at
    # designed points (log cadence, boot timing), each with its reason
    def _run_inner(self, state, data_iter, start):
        step = start
        last_save_ok = False
        it = iter(data_iter)
        # Step boundaries into the native interposer when it is live in
        # this process (DLROVER_TT_PORT is the agent's contract): feeds
        # tpu_timer_last_step / step_open_seconds, the hang watchdog's
        # host-progress signal (last_step stayed -1 in product runs
        # before this wiring).
        tt_begin = tt_end = None
        if os.environ.get("DLROVER_TT_PORT"):
            try:
                from ..profiler import pjrt as _pjrt

                # Idempotent: the interposer already inited the core at
                # plugin load; an UNinterposed worker inits it here so
                # the agent's scraper still sees step progress.
                _pjrt.ensure_core(int(os.environ["DLROVER_TT_PORT"]))
                tt_begin, tt_end = _pjrt.step_begin, _pjrt.step_end
            except Exception as e:  # noqa: BLE001 — aux only
                logger.warning("native step marks unavailable: %s", e)
        while True:
            # bound check BEFORE drawing: a resume at/past max_steps
            # must not consume (and discard) an element of a finite or
            # replayable dataset
            if self.max_steps and step >= self.max_steps:
                break
            if self._stop_requested.is_set():
                # cooperative stop (pool revocation): break BEFORE
                # drawing — the boundary is clean and the tail below
                # stages this step's state for the successor world
                break
            if self._remesh is not None and self._remesh.requested:
                # Stage BEFORE deciding: an accepted world continues
                # from live state; a refusal means the agent restarts
                # us and the staged step is what the successor resumes.
                # Skipped when nothing completed yet (staging the
                # INITIAL state as "step 0 done" would make the
                # successor skip step 0), and when the previous
                # iteration's save of this exact step already landed
                # (a redundant full-model D2H inside the ack budget).
                # An async stage still in flight (or failed) is not a
                # handoff-grade save: confirm it before trusting it.
                # COLLECTIVE verdict — last_save_ok is identical on all
                # hosts (it comes from the save's allgather), so every
                # host reaches this call together, and the AND keeps
                # them on the same branch afterwards.
                if last_save_ok and not self.engine.wait_staged_all(timeout=60.0):
                    last_save_ok = False
                if step > start and not last_save_ok:
                    # 600 x 0.1s: must be able to outlast an in-flight
                    # async stage (whose thread-alive guard makes these
                    # attempts skip), not just a busy persister.
                    for _ in range(600):
                        if self.engine.save_to_memory(step - 1, state):
                            break
                        time.sleep(0.1)
                    else:
                        logger.warning(
                            "remesh handoff: could not stage step %s",
                            step - 1,
                        )
                if self._remesh.apply():
                    if self._replanner is not None:
                        state = self._apply_replan(state)
                    if self._compile_svc is not None:
                        # The likely-next worlds shifted with the
                        # adopted one: re-anticipate so the NEXT remesh
                        # is warm too.
                        self._compile_svc.anticipate(
                            self._anticipation_current()
                        )
            try:
                with span("train.data_wait"):
                    batch = next(it)
            except StopIteration:
                break
            if self.ctx is not None:
                self.ctx.start_step_timer()
            if tt_begin is not None:
                tt_begin(step)
            timed = step == start  # first step = compile + step
            t_step0 = time.monotonic() if timed else 0.0
            with span("train.step_dispatch", step=step):
                state, loss = self.step_fn(state, *batch)
            if timed:
                self._record_boot_step(loss, t_step0)
            if tt_end is not None:
                tt_end(step)
            # Cadence saves stage asynchronously (device-side snapshot +
            # background D2H): the trainer blocks ~ms instead of the
            # full D2H+memcpy. Costs ~+1x the state's bytes of HBM for
            # the snapshot window; a device without that headroom OOMs
            # once and the engine degrades itself back to blocking
            # saves. Handoff saves below (pre-remesh, final) stay
            # blocking — they must be durable before proceeding.
            if self.storage_every and step % self.storage_every == 0:
                last_save_ok = self.engine.save_to_storage(
                    step, state, block=False
                )
            elif step % self.memory_every == 0:
                last_save_ok = self.engine.save_to_memory(
                    step, state, block=False
                )
            else:
                last_save_ok = False
            with span("train.report"):
                self._report(step, loss)
            step += 1
        if last_save_ok and not self.engine.wait_staged_all():
            last_save_ok = False  # async stage failed — redo blocking below
        if step > start and not last_save_ok:
            # In-loop saves skip while the persister holds the shard
            # lock (non-blocking by design); stage the FINAL state with
            # retries so resume continues exactly where training
            # stopped. Skipped when the last in-loop save already
            # landed — re-staging the identical step would cost a
            # redundant full-model D2H + memcpy (+ replica push).
            # Bounded by ATTEMPT COUNT, not wall clock: each attempt is
            # a cross-host collective whose outcome is identical on
            # every host, so a count keeps all hosts in lockstep where
            # per-host deadlines would desynchronize the collective
            # sequence and wedge the world.
            for _ in range(300):
                if self.engine.save_to_memory(step - 1, state):
                    break
                time.sleep(0.1)
            else:
                logger.warning("could not stage the final step %s", step - 1)
        if not self.engine.wait_saving():
            logger.warning("pending checkpoint persists did not complete")
        return state
