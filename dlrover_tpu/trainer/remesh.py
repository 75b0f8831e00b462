"""Soft re-mesh: survive a membership change WITHOUT dying.

The classic elastic model (reference training.py:1262-1278, and this
runtime's default) restarts the worker process on every membership
change: checkpoint to shm, die, re-rendezvous, reboot, restore. The
process reboot is pure overhead when the NEW world has the same shape —
which is exactly the dominant elasticity event (a preempted node's
replacement takes its old slot; every survivor keeps its rank and world
size).

Protocol (files under ``$DLROVER_REMESH_DIR``, all keyed by worker pid
so stale incarnations can never confuse the agent):

- worker writes ``ready_<pid>`` at loop start: "I can soft-remesh".
- agent, on membership change, runs the NEW rendezvous round while the
  worker KEEPS TRAINING, writes the world contract to ``world_<pid>``,
  and sends SIGUSR1.
- worker, at the next step boundary: stages state to shm, applies the
  contract if it is shape-compatible (same num_processes + process_id,
  and either ``jax.distributed`` was never initialized in this process
  or the coordinator is unchanged), and writes ``ack_<pid>``
  (``accepted: true/false``).
- agent: accepted → adopt the new world, nobody died; refused or timed
  out → fall back to the classic hard restart.

The conservative default acceptance means multi-host jax worlds (whose
survivors must re-init the distributed runtime) take the hard path
unless the caller supplies ``on_remesh`` to do better; single-process
worlds (and any world where the coordinator survived) ride through a
node replacement with ZERO downtime for survivors.
"""

import json
import os
import signal
import threading
from typing import Any, Callable, Dict, Optional

from ..common.log import logger

REMESH_DIR_ENV = "DLROVER_REMESH_DIR"


def _jax_distributed_initialized() -> bool:
    # private module, checked against the installed jax 0.9.0
    from jax._src import distributed

    return distributed.global_state.client is not None


class SoftRemesh:
    """Worker-side half of the protocol (one per training loop)."""

    def __init__(
        self,
        ctx,
        on_remesh: Optional[Callable[[Dict[str, Any]], bool]] = None,
    ):
        self._ctx = ctx
        self._on_remesh = on_remesh
        self._dir = os.environ.get(REMESH_DIR_ENV, "")
        self._pid = os.getpid()
        self._flag = threading.Event()
        self._installed = False
        self._prev_handler = None
        self.applied = 0  # worlds adopted without a restart
        # The last adopted world contract — the replan step
        # (loop._apply_replan) reads the device count of the world it
        # is planning for from here when the contract carries one.
        self.last_world: Optional[Dict[str, Any]] = None

    @property
    def available(self) -> bool:
        return bool(self._dir)

    def install(self) -> bool:
        if not self._dir or self._installed:
            return self._installed
        try:
            os.makedirs(self._dir, exist_ok=True)
            self._prev_handler = signal.signal(
                signal.SIGUSR1, lambda *_: self._flag.set()
            )
            with open(self._path("ready"), "w") as f:
                f.write(str(self._pid))
            self._installed = True
        except (OSError, ValueError) as e:
            # ValueError: not the main thread — no handler, no protocol
            logger.warning("soft remesh unavailable: %s", e)
        return self._installed

    def uninstall(self) -> None:
        if not self._installed:
            return
        try:
            signal.signal(signal.SIGUSR1, self._prev_handler or signal.SIG_DFL)
        except (OSError, ValueError):
            pass
        for kind in ("ready", "world", "ack"):
            try:
                os.unlink(self._path(kind))
            except OSError:
                pass
        self._installed = False

    @property
    def requested(self) -> bool:
        return self._flag.is_set()

    def _path(self, kind: str) -> str:
        return os.path.join(self._dir, f"{kind}_{self._pid}")

    # -- application -------------------------------------------------------

    def _acceptable(self, world: Dict[str, Any]) -> bool:
        if self._on_remesh is not None:
            try:
                return bool(self._on_remesh(world))
            except Exception:  # noqa: BLE001 — refuse on hook failure
                logger.exception("on_remesh hook failed; refusing")
                return False
        same_shape = (
            int(world.get("num_processes", -1)) == self._ctx.num_processes
            and int(world.get("process_id", -1)) == self._ctx.process_id
        )
        if not same_shape:
            return False
        if not _jax_distributed_initialized():
            # nothing binds this process to the old coordinator
            return True
        return world.get("coordinator", "") == self._ctx.coordinator

    def apply(self) -> bool:
        """Consume the pending request. True = world adopted (caller
        keeps training); False = refused (the agent will restart us —
        keep training until it does; state is already staged)."""
        self._flag.clear()
        try:
            with open(self._path("world")) as f:
                world = json.load(f)
        except (OSError, ValueError) as e:
            logger.warning("soft remesh: unreadable world contract: %s", e)
            return False
        accepted = self._acceptable(world)
        if accepted:
            self._ctx.coordinator = world.get(
                "coordinator", self._ctx.coordinator
            )
            self._ctx.num_processes = int(
                world.get("num_processes", self._ctx.num_processes)
            )
            self._ctx.process_id = int(
                world.get("process_id", self._ctx.process_id)
            )
            os.environ["DLROVER_COORDINATOR_ADDRESS"] = self._ctx.coordinator
            self.applied += 1
            self.last_world = dict(world)
            logger.info(
                "soft remesh: adopted round %s world (coordinator %s) "
                "without restarting",
                world.get("round"),
                self._ctx.coordinator,
            )
        else:
            logger.info(
                "soft remesh: refusing world %s (shape change or live "
                "distributed runtime); expecting a hard restart",
                {k: world.get(k) for k in ("num_processes", "process_id")},
            )
        try:
            with open(self._path("ack"), "w") as f:
                json.dump({"accepted": accepted}, f)
        except OSError as e:
            logger.warning("soft remesh ack write failed: %s", e)
        return accepted
