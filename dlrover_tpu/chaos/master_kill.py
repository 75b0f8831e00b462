"""Master-kill chaos drill: SIGKILL the coordinating master mid-storm.

After PR 3-9 hardened workers, agents, slices, replicas and the chip
pool against kills, the master was the last single point of failure.
This drill closes the loop: the master runs as a real subprocess with a
state journal (``DLROVER_MASTER_STATE_DIR``), gets SIGKILLed while the
job is stepping, and is restarted by the harness (standing in for the
orchestrator — a k8s Deployment, systemd, the launcher). The claim under
measurement:

- the restarted master **replays its journal** (node tables, rendezvous
  world, kv/sync contents, shard doing/done sets);
- every agent **re-attaches under the epoch fence** — zero worker
  process restarts when the recovered world is unchanged;
- the coordination outage is measured as ``master_mttr_s`` (SIGKILL →
  the restarted master serving an advancing watermark again) with the
  replay phase attributed separately (``master_replay_s`` through the
  recovery spool).

Two shapes share the protocol code:

- :func:`run_master_kill_storm` — the full scenario: real ``tpurun``
  agent processes supervising real tiny-GPT trainers (the goodput
  storm's trainer), master killed between their steps. Slow (jax
  compiles); the ``master_kill`` chaos scenario and the bench storm
  section run this.
- :func:`run_master_kill_synthetic` — tier-1 shape: the same subprocess
  master, but scripted agent threads (no jax) driving the REAL
  ``MasterClient`` epoch fence and the REAL ``reattach_world`` protocol
  at a fast step cadence. Seconds, not minutes.
"""

import os
import signal
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional

from ..agent.rendezvous import find_free_port
from ..common.log import logger

_HTTP = "http"  # deterministic same-port rebind (SO_REUSEADDR listener)


def _spawn_master(
    port: int,
    num_workers: int,
    job_name: str,
    env: Dict[str, str],
    log_path: str,
) -> subprocess.Popen:
    cmd = [
        sys.executable,
        "-m",
        "dlrover_tpu.master.main",
        "--job_name",
        job_name,
        "--num_workers",
        str(num_workers),
        "--port",
        str(port),
        "--service_type",
        _HTTP,
    ]
    log = open(log_path, "ab")
    try:
        proc = subprocess.Popen(
            cmd,
            env=env,
            stdout=log,
            stderr=subprocess.STDOUT,
            start_new_session=True,
        )
    finally:
        log.close()
    return proc  # every caller reaps through _kill_group(proc)


def _kill_group(proc: subprocess.Popen) -> None:
    try:
        os.killpg(os.getpgid(proc.pid), signal.SIGKILL)
    except (ProcessLookupError, PermissionError, OSError):
        try:
            proc.kill()
        except OSError:
            pass
    try:
        proc.wait(10)
    except (subprocess.TimeoutExpired, OSError):
        pass


def _new_client(addr: str, node_id: int = 99, retries: int = 1):
    # retries=1: the surrounding poll loops own the retry cadence, and a
    # fat per-call retry budget would inflate the measured MTTR.
    from ..rpc.client import MasterClient

    return MasterClient(
        master_addr=addr, node_id=node_id, service_type=_HTTP,
        retries=retries,
    )


def _wait_master_ready(addr: str, deadline: float) -> bool:
    while time.time() < deadline:
        try:
            _new_client(addr).get_job_status()
            return True
        except Exception as e:  # noqa: BLE001 — probed until the deadline
            logger.debug("master not serving yet: %r", e)
            time.sleep(0.1)
    return False


def _last_step(client) -> int:
    try:
        return int(client.get_job_status().last_step)
    except Exception as e:  # noqa: BLE001 — dark master = no progress
        logger.debug("job status probe failed: %r", e)
        return -1


def _wait_step(client, target: int, deadline: float) -> Optional[int]:
    while time.time() < deadline:
        step = _last_step(client)
        if step >= target:
            return step
        time.sleep(0.1)
    return None


# ---------------------------------------------------------------------------
# Synthetic drill (tier-1): scripted agents, real fence + re-attach code.
# ---------------------------------------------------------------------------


class _ScriptedAgent(threading.Thread):
    """A no-jax stand-in for (agent + worker): joins the REAL rendezvous,
    heartbeats, reports steps, and runs the REAL epoch-fenced re-attach
    (``reattach_world``) when its client observes a master restart. Its
    "worker" is the step counter — a restart outcome would zero the
    drill's zero-worker-restarts claim."""

    def __init__(self, addr: str, rank: int, step_sleep: float):
        super().__init__(name=f"scripted-agent-{rank}", daemon=True)
        from ..agent.rendezvous import MasterRendezvousHandler
        from ..common.constants import RendezvousName

        self.rank = rank
        self.step_sleep = step_sleep
        self.stop_evt = threading.Event()
        self.client = _new_client(addr, node_id=rank)
        self.handler = MasterRendezvousHandler(
            RendezvousName.TRAINING,
            node_rank=rank,
            client=self.client,
            rdzv_timeout=60.0,
            poll_interval=0.05,
        )
        self.world = None
        self.step = 0
        self.outcomes: List[str] = []
        self.worker_restarts = 0
        self.report_failures = 0
        self.errors: List[str] = []
        self._epoch_bumped = threading.Event()
        self.client.add_epoch_listener(
            lambda old, new: self._epoch_bumped.set()
        )

    def run(self) -> None:
        from ..common.constants import NodeStatus

        try:
            self.world = self.handler.next_rendezvous()
            self.client.report_node_status(NodeStatus.RUNNING)
            self.client.join_sync("master_kill_barrier", node_rank=self.rank)
        except Exception as e:  # noqa: BLE001 — surfaced via errors
            self.errors.append(f"boot: {e!r}")
            return
        while not self.stop_evt.is_set():
            self.step += 1
            try:
                self.client.report_training_step(self.step)
            except Exception:  # noqa: BLE001 — dark master; steps continue
                # The worker does not depend on the master between
                # rendezvous — the step counter keeps moving, exactly
                # like a live JAX worker through a master outage.
                self.report_failures += 1
            if self._epoch_bumped.is_set():
                self._epoch_bumped.clear()
                self._reattach()
            self.stop_evt.wait(self.step_sleep)

    def _reattach(self) -> None:
        from ..agent.rendezvous import reattach_world

        try:
            outcome, world = reattach_world(self.handler, self.world)
        except Exception as e:  # noqa: BLE001 — surfaced via errors
            self.errors.append(f"reattach: {e!r}")
            return
        self.outcomes.append(outcome)
        if outcome == "restart":
            self.worker_restarts += 1
            self.world = world
        elif outcome == "matched":
            self.world = world


def run_master_kill_synthetic(
    workdir: str,
    num_agents: int = 2,
    kill_step: int = 30,
    settle_steps: int = 30,
    step_sleep: float = 0.05,
    timeout_s: float = 120.0,
    master_fault_plan: str = "",
) -> Optional[Dict[str, object]]:
    """Tier-1 master-kill drill; returns the measured result or None on
    timeout. ``master_fault_plan`` rides ``DLROVER_FAULT_PLAN`` into the
    master subprocess (e.g. a ``master.boot.replay`` delay)."""
    os.makedirs(workdir, exist_ok=True)
    state_dir = os.path.join(workdir, "state")
    recovery_dir = os.path.join(workdir, "recovery")
    os.makedirs(recovery_dir, exist_ok=True)
    port = find_free_port()
    addr = f"127.0.0.1:{port}"
    job = f"master_kill_syn_{os.getpid()}"
    env = dict(
        os.environ,
        DLROVER_MASTER_STATE_DIR=state_dir,
        DLROVER_RECOVERY_DIR=recovery_dir,
        DLROVER_MASTER_SERVICE_TYPE=_HTTP,
        # Replayed shard state reconciles fast in a compressed drill.
        DLROVER_MASTER_REATTACH_GRACE_S="2.0",
        PYTHONPATH=os.pathsep.join(sys.path),
    )
    if master_fault_plan:
        env["DLROVER_FAULT_PLAN"] = master_fault_plan
    deadline = time.time() + timeout_s
    master = _spawn_master(
        port, num_agents, job, env, os.path.join(workdir, "master.log")
    )
    agents: List[_ScriptedAgent] = []
    try:
        if not _wait_master_ready(addr, deadline):
            return None
        probe = _new_client(addr)
        agents = [
            _ScriptedAgent(addr, rank, step_sleep)
            for rank in range(num_agents)
        ]
        for agent in agents:
            agent.start()
        if _wait_step(probe, kill_step, deadline) is None:
            return None
        # A kv marker + a finished barrier: both must survive the kill
        # through the journal (the kv/sync round-trip, end to end).
        probe.kv_store_set("master_kill/marker", b"journaled")
        step_at_kill = _last_step(probe)
        t_kill = time.time()
        _kill_group(master)
        master = _spawn_master(
            port, num_agents, job, env, os.path.join(workdir, "master.log")
        )
        if not _wait_master_ready(addr, deadline):
            return None
        # MTTR = kill → the restarted master serving an ADVANCING
        # watermark (replay + agents re-reporting steps), the same
        # watermark definition every other storm uses.
        fresh = _new_client(addr)
        if _wait_step(fresh, step_at_kill + 1, deadline) is None:
            return None
        master_mttr_s = time.time() - t_kill
        target = step_at_kill + settle_steps
        if _wait_step(fresh, target, deadline) is None:
            return None
        end_t = time.time()
        kv_ok = fresh.kv_store_get("master_kill/marker") == b"journaled"
        sync_ok = fresh.sync_finished("master_kill_barrier")
        window = max(1e-6, end_t - t_kill)
        made = _last_step(fresh) - step_at_kill
        expected = window / step_sleep
        result: Dict[str, object] = {
            "master_mttr_s": round(master_mttr_s, 2),
            "master_kill_goodput": round(
                min(1.0, made / max(1.0, expected)), 4
            ),
            "steps": _last_step(fresh),
            "epoch": max(a.client.master_epoch for a in agents),
            "worker_restarts": sum(a.worker_restarts for a in agents),
            "reattach_outcomes": sorted(
                o for a in agents for o in a.outcomes
            ),
            "agent_errors": [e for a in agents for e in a.errors],
            "kv_survived": kv_ok,
            "sync_survived": bool(sync_ok),
        }
        from ..attribution.recovery import aggregate

        result.update(
            {
                k: v
                for k, v in aggregate(recovery_dir).items()
                if k.startswith("master_") or k == "reattach_s"
            }
        )
        return result
    finally:
        for agent in agents:
            agent.stop_evt.set()
        for agent in agents:
            agent.join(timeout=10)
        _kill_group(master)


# ---------------------------------------------------------------------------
# Full storm (scenario / bench): real agents, real trainers.
# ---------------------------------------------------------------------------


def _worker_pid(namespace: str) -> Optional[int]:
    """Live worker pid recorded for an IPC namespace (pidfile written by
    agent/worker.py), or None when absent/dead."""
    pidfile_dir = os.getenv(
        "DLROVER_PIDFILE_DIR", os.path.join("/tmp", "dlrover_tpu", "workers")
    )
    try:
        parts = open(os.path.join(pidfile_dir, f"{namespace}.pid")).read().split()
        pid = int(parts[0])
        os.kill(pid, 0)
        return pid
    except (OSError, ValueError, IndexError):
        return None


def run_master_kill_storm(
    workdir: str,
    num_workers: int = 2,
    kill_step: int = 20,
    settle_steps: int = 12,
    step_sleep: float = 0.2,
    storage_every: int = 5,
    timeout_s: float = 420.0,
    job_name: str = "",
    master_fault_plan: str = "",
    prewarm: bool = True,
) -> Optional[Dict[str, object]]:
    """Full master-kill storm: subprocess master + real ``tpurun`` agents
    + real tiny-GPT trainers. The master is SIGKILLed at ``kill_step``
    and restarted; the result reports ``master_mttr_s``,
    ``master_kill_goodput`` (productive step fraction of the kill→end
    window), the journal epoch, and ``worker_restarts`` measured from
    the workers' pidfiles — the acceptance number is 0."""
    from .goodput_storm import _TRAINER_TEMPLATE
    from .harness import cleanup_namespaces

    os.makedirs(workdir, exist_ok=True)
    job = job_name or f"master_kill_{os.getpid()}"
    cleanup_namespaces(job, num_workers)
    state_dir = os.path.join(workdir, "state")
    recovery_dir = os.path.join(workdir, "recovery")
    ckpt_dir = os.path.join(workdir, "ckpt")
    for d in (recovery_dir, ckpt_dir):
        os.makedirs(d, exist_ok=True)
    script = os.path.join(workdir, "storm_trainer.py")
    with open(script, "w") as f:
        f.write(_TRAINER_TEMPLATE)
    if prewarm:
        prewarm_env = dict(
            os.environ,
            STORM_PREWARM="1",
            PYTHONPATH=os.pathsep.join(sys.path),
        )
        subprocess.run(
            [sys.executable, script],
            env=prewarm_env,
            timeout=120,
            capture_output=True,
        )

    port = find_free_port()
    addr = f"127.0.0.1:{port}"
    master_env = dict(
        os.environ,
        DLROVER_MASTER_STATE_DIR=state_dir,
        DLROVER_RECOVERY_DIR=recovery_dir,
        DLROVER_MASTER_SERVICE_TYPE=_HTTP,
        DLROVER_MASTER_REATTACH_GRACE_S="5.0",
        PYTHONPATH=os.pathsep.join(sys.path),
    )
    if master_fault_plan:
        master_env["DLROVER_FAULT_PLAN"] = master_fault_plan
    deadline = time.time() + timeout_s
    master = _spawn_master(
        port, num_workers, job, master_env,
        os.path.join(workdir, "master.log"),
    )
    agent_procs: List[subprocess.Popen] = []
    namespaces = [f"{job}_n{i}" for i in range(num_workers)]
    try:
        if not _wait_master_ready(addr, deadline):
            return None
        from ..common.constants import NodeEnv

        for rank in range(num_workers):
            env = dict(
                os.environ,
                PYTHONPATH=os.pathsep.join(sys.path),
                DLROVER_RECOVERY_DIR=recovery_dir,
                DLROVER_MASTER_SERVICE_TYPE=_HTTP,
                DLROVER_IPC_NAMESPACE=namespaces[rank],
                DLROVER_LOCAL_DEVICES="1",
                STORM_CKPT_DIR=ckpt_dir,
                STORM_STEP_SLEEP=str(step_sleep),
                STORM_STORAGE_EVERY=str(storage_every),
                STORM_MAX_STEPS=str((kill_step + settle_steps) * 50),
            )
            env[NodeEnv.MASTER_ADDR] = addr
            env[NodeEnv.JOB_NAME] = job
            env[NodeEnv.NODE_ID] = str(rank)
            env[NodeEnv.NODE_RANK] = str(rank)
            log = open(os.path.join(workdir, f"agent_{rank}.log"), "ab")
            try:
                agent_procs.append(
                    subprocess.Popen(
                        [
                            sys.executable,
                            "-m",
                            "dlrover_tpu.launcher.elastic_run",
                            "--nnodes",
                            str(num_workers),
                            "--monitor_interval",
                            "0.5",
                            "--max_restarts",
                            "3",
                            script,
                        ],
                        env=env,
                        stdout=log,
                        stderr=subprocess.STDOUT,
                        start_new_session=True,
                    )
                )
            finally:
                log.close()
        probe = _new_client(addr)
        if _wait_step(probe, kill_step, deadline) is None:
            logger.warning("master-kill storm: never reached kill step")
            return None
        probe.kv_store_set("master_kill/marker", b"journaled")
        pids_before = {ns: _worker_pid(ns) for ns in namespaces}
        step_at_kill = _last_step(probe)
        t_kill = time.time()
        logger.info(
            "master-kill storm: SIGKILL master pid=%s at step %s",
            master.pid,
            step_at_kill,
        )
        _kill_group(master)
        master = _spawn_master(
            port, num_workers, job, master_env,
            os.path.join(workdir, "master.log"),
        )
        if not _wait_master_ready(addr, deadline):
            return None
        fresh = _new_client(addr)
        if _wait_step(fresh, step_at_kill + 1, deadline) is None:
            return None
        master_mttr_s = time.time() - t_kill
        if _wait_step(fresh, step_at_kill + settle_steps, deadline) is None:
            return None
        end_t = time.time()
        pids_after = {ns: _worker_pid(ns) for ns in namespaces}
        worker_restarts = sum(
            1
            for ns in namespaces
            if pids_before.get(ns) is not None
            and pids_after.get(ns) != pids_before.get(ns)
        )
        window = max(1e-6, end_t - t_kill)
        made = _last_step(fresh) - step_at_kill
        result: Dict[str, object] = {
            "master_mttr_s": round(master_mttr_s, 2),
            "master_kill_goodput": round(
                min(1.0, made / max(1.0, window / step_sleep)), 4
            ),
            "steps": _last_step(fresh),
            "worker_restarts": worker_restarts,
            "kv_survived": fresh.kv_store_get("master_kill/marker")
            == b"journaled",
        }
        try:
            from ..master.persistence import MasterStateStore

            result["epoch"] = MasterStateStore(state_dir).read_epoch()
        except Exception as e:  # noqa: BLE001 — diagnostics only
            logger.warning("epoch read failed: %s", e)
        from ..attribution.recovery import aggregate

        result.update(aggregate(recovery_dir))
        return result
    finally:
        for proc in agent_procs:
            _kill_group(proc)
        _kill_group(master)
        from ..agent.worker import kill_worker_by_pidfile

        for ns in namespaces:
            kill_worker_by_pidfile(ns)


def main(argv=None) -> int:
    import argparse
    import json
    import tempfile

    parser = argparse.ArgumentParser(
        description="master-kill crash-tolerance drill"
    )
    parser.add_argument("--workdir", default="")
    parser.add_argument(
        "--synthetic",
        action="store_true",
        help="scripted agents, no jax (the tier-1 shape)",
    )
    parser.add_argument("--num-workers", type=int, default=2)
    ns = parser.parse_args(argv)
    workdir = ns.workdir or tempfile.mkdtemp(prefix="master_kill_")
    if ns.synthetic:
        result = run_master_kill_synthetic(workdir, num_agents=ns.num_workers)
    else:
        result = run_master_kill_storm(workdir, num_workers=ns.num_workers)
    print(json.dumps(result))
    return 0 if result else 1


if __name__ == "__main__":
    sys.exit(main())
