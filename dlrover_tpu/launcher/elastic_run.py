"""``tpurun`` — the elastic launcher CLI.

Reference: ``dlrover-run`` (dlrover/trainer/torch/elastic_run.py):
``parse_args`` extending torchrun's parser (:124-217), ``ElasticLaunch``
(:220-266), ``wait_pre_check`` (:269-297), standalone local-master spawn
(:300-329), master reachability check (:450-517) and config merge
(:408-447).

TPU-native shape: one agent per host supervising one JAX process.
``tpurun`` locates (or, standalone, spawns) the job master, waits for the
pre-check verdict, optionally runs the node health check, then hands off
to :class:`ElasticTrainingAgent`, which feeds every rendezvous round's
``jax.distributed.initialize`` triple to the worker via the env contract.
"""

import argparse
import os
import shlex
import subprocess
import sys
import tempfile
import time
import uuid
from typing import List, Optional, Tuple

from ..agent.config import ElasticLaunchConfig
from ..agent.training_agent import ElasticTrainingAgent
from ..attribution.recovery import default_recovery_dir
from ..common.constants import (
    Accelerators,
    DefaultValues,
    NodeEnv,
    PreCheckStatus,
)
from ..common.log import logger
from ..rpc.client import MasterClient


def parse_args(args: Optional[List[str]] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        prog="tpurun",
        description="Launch an elastic, fault-tolerant JAX/TPU training job.",
    )
    parser.add_argument(
        "--standalone",
        action="store_true",
        help="run a local job master in a subprocess (single machine)",
    )
    parser.add_argument(
        "--nnodes",
        default="1",
        help="number of hosts: N or MIN:MAX for an elastic range",
    )
    parser.add_argument(
        "--nproc_per_node",
        type=int,
        default=0,
        help="local device count (0 = all local chips)",
    )
    parser.add_argument(
        "--node_unit",
        type=int,
        default=1,
        help="valid world sizes are multiples of this (hosts per slice)",
    )
    parser.add_argument("--node_rank", type=int, default=-1, help="this host's rank")
    parser.add_argument(
        "--precheck",
        type=int,
        default=0,
        choices=[0, 1, 2],
        help="0: skip master pre-check wait; 1: wait; 2: wait and fail fast",
    )
    parser.add_argument(
        "--network-check",
        action="store_true",
        dest="network_check",
        help="run the pairwise node health check before training",
    )
    parser.add_argument(
        "--comm-perf-test",
        action="store_true",
        dest="comm_perf_test",
        help="also benchmark collective throughput during the node check",
    )
    parser.add_argument(
        "--exclude-straggler",
        action="store_true",
        dest="exclude_straggler",
        help="exit (for relaunch) when this node is flagged a straggler",
    )
    parser.add_argument(
        "--auto_config",
        action="store_true",
        help="fill node counts from the scheduler env contract",
    )
    parser.add_argument(
        "--auto_tunning",
        action="store_true",
        help="poll master for parallelism/batch tuning configs",
    )
    parser.add_argument(
        "--save_at_breakpoint",
        action=argparse.BooleanOptionalAction,
        default=DefaultValues.SAVE_AT_BREAKPOINT,
        help="persist the staged shm checkpoint when workers fail",
    )
    parser.add_argument(
        "--accelerator",
        default=Accelerators.TPU,
        choices=[Accelerators.TPU, Accelerators.CPU],
    )
    parser.add_argument(
        "--numa-affinity",
        action="store_true",
        dest="numa_affinity",
        help="pin each worker to the TPU-local NUMA node's CPUs "
        "(no-op when the PCI topology is not visible)",
    )
    parser.add_argument(
        "--profile",
        default="auto",
        choices=["auto", "on", "off"],
        help="native PJRT profiling of the worker (auto = on for TPU): "
        "the agent loads the interposer into the worker via the env "
        "contract, scrapes its /metrics, and rank 0 runs the cluster "
        "profiler daemon",
    )
    parser.add_argument(
        "--max_restarts",
        type=int,
        default=DefaultValues.MAX_RELAUNCH_COUNT,
        help="in-place worker restart budget before asking for relaunch",
    )
    parser.add_argument(
        "--monitor_interval",
        type=float,
        default=DefaultValues.MONITOR_INTERVAL_S,
        help="agent supervision poll seconds (worker health + membership "
        "changes); lower = faster elastic reaction, more master RPCs",
    )
    parser.add_argument(
        "--training_port",
        type=int,
        default=0,
        help="base port for the jax.distributed coordinator (0 = free port)",
    )
    parser.add_argument(
        "--sync-input",
        action="store_true",
        dest="sync_input",
        help="disable the train loop's double-buffered input prefetch "
        "(exports DLROVER_INPUT_PREFETCH=0): the loop then draws each "
        "batch synchronously, for sources that must not observe a draw "
        "ahead of the step that consumes it",
    )
    parser.add_argument("--log_dir", default=None, help="worker log directory")
    parser.add_argument(
        "-m",
        "--module",
        action="store_true",
        help="entrypoint is a python module (python -m style)",
    )
    parser.add_argument("entrypoint", help="training script or module")
    parser.add_argument(
        "entry_args", nargs=argparse.REMAINDER, help="args for the entrypoint"
    )
    return parser.parse_args(args)


def parse_nnodes(spec: str) -> Tuple[int, int]:
    if ":" in spec:
        lo, hi = spec.split(":", 1)
        return int(lo), int(hi)
    n = int(spec)
    return n, n


def config_from_args(ns: argparse.Namespace) -> ElasticLaunchConfig:
    min_nodes, max_nodes = parse_nnodes(ns.nnodes)
    nproc = ns.nproc_per_node
    if nproc <= 0:
        nproc = _local_device_count()
    node_rank = ns.node_rank
    if node_rank < 0:
        node_rank = int(os.environ.get(NodeEnv.NODE_RANK, "0"))
    node_id = int(os.environ.get(NodeEnv.NODE_ID, str(node_rank)))
    config = ElasticLaunchConfig(
        min_nodes=min_nodes,
        max_nodes=max_nodes,
        node_unit=ns.node_unit,
        node_id=node_id,
        node_rank=node_rank,
        local_world_size=nproc,
        entrypoint=ns.entrypoint,
        entry_args=list(ns.entry_args),
        run_module=ns.module,
        master_addr=os.environ.get(NodeEnv.MASTER_ADDR, ""),
        # Propagate the transport into the worker env contract: the
        # agent's own client reads the env directly, but worker_env()
        # re-exports config.master_service_type — leaving it at the
        # default silently pointed every trainer of an HTTP-master job
        # at a gRPC transport (step reports died at debug level).
        master_service_type=os.environ.get(
            NodeEnv.MASTER_SERVICE_TYPE, DefaultValues.SERVICE_TYPE
        ),
        job_name=os.environ.get(NodeEnv.JOB_NAME, "local_job"),
        accelerator=ns.accelerator,
        network_check=ns.network_check,
        comm_perf_test=ns.comm_perf_test,
        exclude_straggler=ns.exclude_straggler,
        auto_config=ns.auto_config,
        auto_tunning=ns.auto_tunning,
        max_restarts=ns.max_restarts,
        save_at_breakpoint=ns.save_at_breakpoint,
        training_port=ns.training_port,
        log_dir=ns.log_dir,
        numa_affinity=ns.numa_affinity,
        profile=ns.profile,
        monitor_interval=ns.monitor_interval,
        input_prefetch=not ns.sync_input,
    )
    config.auto_configure_params()
    return config


def _local_device_count() -> int:
    """Local chip count without initializing the JAX runtime in the agent
    process (the worker owns the devices; reference keeps the agent off
    the accelerator the same way)."""
    env_count = os.environ.get("TPU_NUM_DEVICES") or os.environ.get(
        "DLROVER_LOCAL_DEVICES"
    )
    if env_count:
        return int(env_count)
    return 1


class LocalMasterHandle:
    """A standalone-mode master subprocess (reference elastic_run.py:300)."""

    def __init__(self, proc: subprocess.Popen, addr: str):
        self.proc = proc
        self.addr = addr

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(10)
            except subprocess.TimeoutExpired:
                self.proc.kill()


def launch_local_master(
    num_workers: int, node_unit: int = 1, job_name: str = "standalone"
) -> LocalMasterHandle:
    port_file = os.path.join(
        tempfile.gettempdir(), f"dlrover_master_{uuid.uuid4().hex[:8]}.port"
    )
    cmd = [
        sys.executable,
        "-m",
        "dlrover_tpu.master.main",
        "--job_name",
        job_name,
        "--num_workers",
        str(num_workers),
        "--node_unit",
        str(node_unit),
        "--port_file",
        port_file,
    ]
    logger.info("starting standalone master: %s", shlex.join(cmd))
    proc = subprocess.Popen(cmd, start_new_session=True)
    deadline = time.time() + 60
    while time.time() < deadline:
        if os.path.exists(port_file):
            with open(port_file) as f:
                content = f.read().strip()
            if content:
                os.unlink(port_file)
                return LocalMasterHandle(proc, f"127.0.0.1:{content}")
        if proc.poll() is not None:
            raise RuntimeError(
                f"standalone master exited rc={proc.returncode} before serving"
            )
        time.sleep(0.2)
    proc.terminate()
    raise RuntimeError("standalone master did not start within 60s")


def wait_pre_check(
    client: MasterClient, level: int, timeout: float = 600.0
) -> bool:
    """Block until the master's pre-check chain passes (reference :269-297).

    level 1 tolerates a missing/unsupported pre-check; level 2 fails the
    launch when the check reports FAILED.
    """
    if level <= 0:
        return True
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            resp = client.get_pre_check_result()
        except Exception as e:
            logger.warning("pre-check query failed: %s", e)
            time.sleep(2)
            continue
        if resp.status == PreCheckStatus.PASSED:
            return True
        if resp.status == PreCheckStatus.FAILED:
            if level >= 2:
                logger.error("master pre-check failed: %s", resp.reason)
                return False
            logger.warning(
                "master pre-check failed (%s); proceeding at level 1",
                resp.reason,
            )
            return True
        time.sleep(2)
    logger.error("pre-check did not pass within %.0fs", timeout)
    return level < 2


def merge_elastic_config_from_master(
    client: MasterClient, config: ElasticLaunchConfig
) -> None:
    """Master-side overrides win over CLI defaults (reference :408-447)."""
    try:
        run_config = client.get_elastic_run_config()
    except Exception as e:  # noqa: BLE001 — master overrides are optional
        logger.debug("no master run-config overrides: %r", e)
        return
    if not run_config:
        return
    if "network_check" in run_config:
        config.network_check = run_config["network_check"] in ("1", "true", "True")
    if "node_unit" in run_config:
        config.node_unit = int(run_config["node_unit"])
    if "save_at_breakpoint" in run_config:
        config.save_at_breakpoint = run_config["save_at_breakpoint"] in (
            "1",
            "true",
            "True",
        )


class ElasticLaunch:
    """Callable launch wrapper (reference elastic_run.py:220-266)."""

    def __init__(self, config: ElasticLaunchConfig):
        self._config = config

    def __call__(self) -> int:
        client = MasterClient.singleton()
        merge_elastic_config_from_master(client, self._config)
        if self._config.network_check:
            from .node_check import run_node_check

            if not run_node_check(self._config, client):
                return 1
        agent = ElasticTrainingAgent(self._config)
        return agent.run()


def run(ns: argparse.Namespace) -> int:
    # Crash-safe span flushing: an agent dying on SIGTERM/exception must
    # land its buffered events first (reference error_handler.py:26).
    from ..common.error_handler import init_error_handler

    init_error_handler()
    config = config_from_args(ns)
    # start-up records of the agent and its workers: <log_dir>/startup
    # unless DLROVER_RECOVERY_DIR names another place
    default_recovery_dir(config.log_dir)
    master_handle: Optional[LocalMasterHandle] = None
    if ns.standalone and not config.master_addr:
        master_handle = launch_local_master(
            num_workers=config.max_nodes,
            node_unit=config.node_unit,
            job_name=config.job_name,
        )
        config.master_addr = master_handle.addr
        os.environ[NodeEnv.MASTER_ADDR] = master_handle.addr
    if not config.master_addr:
        logger.error(
            "no master: set %s or pass --standalone", NodeEnv.MASTER_ADDR
        )
        return 2
    os.environ[NodeEnv.MASTER_ADDR] = config.master_addr
    os.environ.setdefault(NodeEnv.NODE_ID, str(config.node_id))
    try:
        client = MasterClient.singleton()
        if not wait_pre_check(client, ns.precheck):
            return 1
        return ElasticLaunch(config)()
    finally:
        if master_handle is not None:
            master_handle.stop()


def main(args: Optional[List[str]] = None) -> int:
    return run(parse_args(args))


if __name__ == "__main__":
    sys.exit(main())
