"""Elastic bootstrap for the JAX training process.

The agent hands this process its place in the world via the
``NodeEnv`` contract (reference: per-node env in
dlrover/python/common/constants.py NodeEnv, consumed by torchrun in the
reference; consumed by ``jax.distributed.initialize`` here). Every
restart of the process is a fresh world: process_id / num_processes may
differ from the previous incarnation, and the training script is
expected to rebuild its Mesh from ``jax.devices()`` after ``initialize``.
"""

import os
import time
from dataclasses import dataclass
from typing import Optional

from ..common.constants import NodeEnv
from ..common.log import logger
from ..rpc.client import MasterClient


@dataclass
class ElasticContext:
    """This process's coordinates in the elastic world."""

    node_id: int = 0
    node_rank: int = 0
    num_processes: int = 1
    process_id: int = 0
    coordinator: str = ""
    restart_count: int = 0
    master_addr: str = ""
    job_name: str = "local_job"
    auto_tunning: bool = False

    _client: Optional[MasterClient] = None
    _step_t0: float = 0.0

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0

    @classmethod
    def from_env(cls) -> "ElasticContext":
        env = os.environ
        return cls(
            node_id=int(env.get(NodeEnv.NODE_ID, "0")),
            node_rank=int(env.get(NodeEnv.NODE_RANK, "0")),
            num_processes=int(env.get(NodeEnv.NUM_PROCESSES, "1")),
            process_id=int(env.get(NodeEnv.PROCESS_ID, "0")),
            coordinator=env.get(NodeEnv.COORDINATOR_ADDRESS, ""),
            restart_count=int(env.get(NodeEnv.RESTART_COUNT, "0")),
            master_addr=env.get(NodeEnv.MASTER_ADDR, ""),
            job_name=env.get(NodeEnv.JOB_NAME, "local_job"),
            auto_tunning=env.get(NodeEnv.AUTO_TUNNING, "") == "1",
        )

    def world_device_count(self) -> int:
        """Global device count of the CURRENT world — the input the
        elastic replanner's rung ladder is enumerated for. Prefers the
        live backend's view; falls back to num_processes × local device
        count when jax is not up yet (or its world is stale mid-remesh).
        """
        try:
            import jax

            n = jax.device_count()
            if n > 0:
                return n
        except Exception as e:  # noqa: BLE001 — backend not initialized
            logger.debug("jax device count unavailable (%s); using env", e)
        local = int(os.environ.get("DLROVER_LOCAL_DEVICES", "0") or 0)
        return max(1, self.num_processes * max(1, local))

    def initialize_jax(self) -> None:
        """Bring up the multi-host JAX runtime for this world, to a live
        backend: the start-up phase ``startup.backend`` ends with the
        first ``jax.devices()``, so that the PJRT client's seconds are
        named here and not inside whatever touches a device first.

        Single-process worlds skip ``jax.distributed`` entirely — that is
        also the standalone/test path where the process uses the local
        (or virtual CPU) devices directly.
        """
        from ..observability.spans import startup_span

        with startup_span("backend"):
            self._initialize_distributed()
            import jax

            jax.devices()

    def _initialize_distributed(self) -> None:
        from ..profiler.stack_dump import (
            install_stack_dump_handler,
            start_ring_dump_watcher,
        )

        # Hang post-mortems: the agent's SIGUSR2 lands here even when the
        # process is wedged inside a blocked collective.
        install_stack_dump_handler()
        if os.environ.get("DLROVER_TT_PORT"):
            # Profiled worker: serve trace-ring dump requests (a thread,
            # so it works even while the main thread is wedged — the
            # exact moment a timeline is wanted).
            start_ring_dump_watcher()
        if self.num_processes <= 1 or not self.coordinator:
            logger.info("single-process world; skipping jax.distributed")
            return
        import jax

        logger.info(
            "jax.distributed.initialize(coordinator=%s, num_processes=%s, "
            "process_id=%s)",
            self.coordinator,
            self.num_processes,
            self.process_id,
        )
        jax.distributed.initialize(
            coordinator_address=self.coordinator,
            num_processes=self.num_processes,
            process_id=self.process_id,
        )

    # -- master control-plane helpers ------------------------------------

    @property
    def client(self) -> Optional[MasterClient]:
        if self._client is None and self.master_addr:
            self._client = MasterClient.singleton()
        return self._client

    def report_step(
        self, step: int, elapsed_s: float = 0.0, tokens_per_s: float = 0.0
    ) -> None:
        """Feed the master's PerfMonitor / hang detector. When
        ``start_step_timer`` was called for this step, the elapsed time
        is filled in automatically."""
        if elapsed_s == 0.0 and self._step_t0 > 0.0:
            elapsed_s = time.monotonic() - self._step_t0
        # Always drop the timer: a stale t0 surviving an explicit
        # elapsed_s report would span multiple steps at the next
        # auto-timed report and skew the PerfMonitor.
        self._step_t0 = 0.0
        if self.client is None:
            return
        try:
            self.client.report_training_step(
                step=step, elapsed_s=elapsed_s, tokens_per_s=tokens_per_s
            )
        except Exception as e:
            logger.debug("step report failed: %s", e)

    def start_step_timer(self) -> None:
        # monotonic: an NTP step between here and report_step must not
        # produce negative/inflated durations for the PerfMonitor
        self._step_t0 = time.monotonic()

    def start_config_tuner(self, dataloader=None):
        """Start the auto-tuning poller when the launcher enabled it
        (``tpurun --auto_tunning``); returns the tuner or None."""
        if not self.auto_tunning or self.client is None:
            return None
        from .config_tuner import ParalConfigTuner

        tuner = ParalConfigTuner(client=self.client)
        if dataloader is not None:
            tuner.attach_dataloader(dataloader)
        tuner.start()
        return tuner


_context: Optional[ElasticContext] = None


def elastic_context(initialize: bool = True) -> ElasticContext:
    """Process-wide singleton; builds from env and (optionally) brings up
    the JAX distributed runtime on first call."""
    global _context
    if _context is None:
        # One persistent compile cache for every incarnation (the
        # placement rule lives in common/compile_cache.py): applied
        # here, before any compilation, so a restart's re-compile is a
        # cache read.
        from ..attribution.recovery import startup_from_process_start
        from ..common.compile_cache import enable_compile_cache

        enable_compile_cache()  # imports JAX where the script has not
        startup_from_process_start("imports")
        _context = ElasticContext.from_env()
        if initialize:
            _context.initialize_jax()
    return _context
