"""Pre-flight node health check (agent side).

Reference: ``NodeCheckElasticAgent.run`` (dlrover/python/elastic_agent/
torch/training.py:1584) spawning matmul+allreduce subprocesses
(``trainer/torch/node_check/nvidia_gpu.py:52-84``), with the master's
``NetworkCheckRendezvousManager`` pairing hosts (adjacent pairs, then
fastest-with-slowest) so a both-round failure pins the faulty host, and
stragglers flagged at elapsed > ratio × median (rdzv_manager.py:610-799).

TPU-native check per host:
  1. device check — enumerate local chips, time a bf16 matmul sized to
     land on the MXU (device FLOPs sanity);
  2. intra-host collective — ``psum`` over the local device mesh (ICI on
     a real host, XLA CPU ring in tests);
  3. pair exchange — a KV-store payload round-trip with the pair peer
     assigned by the master (DCN control-plane reachability + latency).

Each round reports (normal, elapsed) to the master; the launcher then
reads fault/straggler verdicts. The device half (1 and 2) runs in a
CHILD process (``python -m dlrover_tpu.launcher.node_check``) that
exits before the round reports: a chip belongs to one process at a
time, so an agent that initialized JAX itself would hold the chip its
worker needs. The agent stays off JAX; the child gets the worker's env
contract, platform pin included, so a probe cannot pass on the CPU when
the job asked for the TPU.
"""

import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional, Tuple

from ..common.constants import NodeCheckConstants, RendezvousName
from ..common.log import logger
from ..rpc.client import MasterClient
from ..agent.config import ElasticLaunchConfig
from ..agent.rendezvous import MasterRendezvousHandler

CHECK_ROUNDS = NodeCheckConstants.CHECK_ROUNDS
_MATMUL_DIM = 1024
_PROBE_TIMEOUT_S = 300.0


def _device_matmul_seconds() -> Tuple[bool, float]:
    """Time a bf16 matmul on every local device; False on any failure."""
    import jax
    import jax.numpy as jnp

    try:
        devices = jax.local_devices()
        if not devices:
            return False, 0.0
        x = jnp.ones((_MATMUL_DIM, _MATMUL_DIM), jnp.bfloat16)
        started = time.monotonic()
        for dev in devices:
            xd = jax.device_put(x, dev)
            (xd @ xd).block_until_ready()
        return True, time.monotonic() - started
    except Exception as e:  # device enumeration/compile failure = faulty
        logger.error("device matmul check failed: %s", e)
        return False, 0.0


def _local_psum(devices):
    """Jitted all-reduce over ``devices`` (a one-axis local mesh)."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(devices), ("d",))
    # tpulint: ignore[mesh-axes] "d" is the health check's single-host probe axis, not a training mesh axis
    psum = lambda x: jax.lax.psum(x, "d")  # noqa: E731
    # tpulint: ignore[mesh-axes] same probe axis
    return jax.jit(jax.shard_map(psum, mesh=mesh, in_specs=P("d"), out_specs=P()))


def _local_collective_seconds() -> Tuple[bool, float]:
    """Time a psum across the local devices (single-host mesh)."""
    import jax
    import jax.numpy as jnp

    try:
        devices = jax.local_devices()
        if len(devices) < 2:
            return True, 0.0
        n = len(devices)
        started = time.monotonic()
        out = _local_psum(devices)(jnp.ones((n, 128)))
        out.block_until_ready()
        return True, time.monotonic() - started
    except Exception as e:
        logger.error("local collective check failed: %s", e)
        return False, 0.0


def _probe_devices_in_child(
    config: ElasticLaunchConfig, comm_perf: bool = False
) -> Dict[str, Tuple[bool, float]]:
    """Run the device checks in a child that owns the chip only for the
    probe's lifetime. A child that dies, hangs or prints no verdict is a
    failed check — never a pass."""
    env = dict(os.environ)
    env.update(config.worker_env())
    cmd = [sys.executable, "-m", "dlrover_tpu.launcher.node_check"]
    if comm_perf:
        cmd.append("--comm-perf")
    failed = {"matmul": (False, 0.0), "collective": (False, 0.0)}
    try:
        proc = subprocess.run(
            cmd,
            env=env,
            capture_output=True,
            text=True,
            timeout=_PROBE_TIMEOUT_S,
        )
    except subprocess.TimeoutExpired:
        logger.error("device probe timed out after %.0fs", _PROBE_TIMEOUT_S)
        return failed
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        logger.error(
            "device probe failed rc=%s: %s",
            proc.returncode,
            proc.stderr[-2000:],
        )
        return failed
    verdict = json.loads(lines[-1])
    return {k: (bool(v[0]), float(v[1])) for k, v in verdict.items()}


def _pair_exchange_seconds(
    client: MasterClient,
    node_rank: int,
    peer_rank: Optional[int],
    wave: int,
    payload_bytes: int = 1 << 16,
    timeout: float = 60.0,
) -> Tuple[bool, float]:
    """KV-store payload round-trip with the pair peer.

    Both members write ``netcheck/<wave>/<rank>`` then poll for the
    peer's key; elapsed covers write + peer visibility, a control-plane
    proxy for DCN reachability (the data-plane equivalent needs a formed
    world, which is what this check gates). Keys are namespaced by the
    rendezvous wave round — unique per join wave across the whole job —
    so a re-run after a node relaunch never reads a stale payload from a
    previous check sequence.
    """
    if peer_rank is None:
        return True, 0.0
    payload = bytes(payload_bytes)
    try:
        started = time.monotonic()
        client.kv_store_set(f"netcheck/{wave}/{node_rank}", payload)
        deadline = started + timeout
        peer_key = f"netcheck/{wave}/{peer_rank}"
        while time.monotonic() < deadline:
            value = client.kv_store_get(peer_key)
            if value:
                return len(value) == payload_bytes, time.monotonic() - started
            time.sleep(0.2)
        logger.error("pair exchange with rank %s timed out", peer_rank)
        return False, time.monotonic() - started
    except Exception as e:
        logger.error("pair exchange failed: %s", e)
        return False, 0.0


def run_node_check(
    config: ElasticLaunchConfig,
    client: Optional[MasterClient] = None,
    matmul_fn=None,
    collective_fn=None,
) -> bool:
    """Run CHECK_ROUNDS rounds of the pre-flight check.

    Returns True when this node may proceed to the training rendezvous;
    False when the master marked it faulty (the launcher exits nonzero so
    the platform replaces the node — reference training.py:1787).

    ``matmul_fn``/``collective_fn`` override the device checks — the
    chaos-test hook for injecting a faulty host without a faulty host.
    """
    client = client or MasterClient.singleton()
    for round_idx in range(CHECK_ROUNDS):
        handler = MasterRendezvousHandler(
            RendezvousName.NETWORK_CHECK,
            node_rank=config.node_rank,
            client=client,
            node_id=config.node_id,
            local_world_size=config.local_world_size,
            rdzv_timeout=config.rdzv_timeout,
        )
        world = handler.next_rendezvous()
        peer = None
        member_ranks = sorted(m.node_rank for m in world.world.values())
        if len(member_ranks) == 2:
            peer = (
                member_ranks[1]
                if member_ranks[0] == config.node_rank
                else member_ranks[0]
            )
        probe: Dict[str, Tuple[bool, float]] = {}
        if matmul_fn is None or collective_fn is None:
            probe = _probe_devices_in_child(
                config, comm_perf=config.comm_perf_test and round_idx == 0
            )
        ok_m, t_m = matmul_fn() if matmul_fn else probe["matmul"]
        ok_c, t_c = collective_fn() if collective_fn else probe["collective"]
        ok_p, t_p = _pair_exchange_seconds(
            client, config.node_rank, peer, world.round
        )
        normal = ok_m and ok_c and ok_p
        elapsed = t_m + t_c + t_p
        # Echo the wave number back: the master owns the wave→check-round
        # mapping, so a restarted check loop cannot desync the rounds.
        client.report_network_check_result(
            normal, elapsed, round=world.round, node_rank=config.node_rank
        )
        logger.info(
            "node check round %s (wave %s): normal=%s elapsed=%.3fs "
            "(matmul=%.3f collective=%.3f pair=%.3f)",
            round_idx,
            world.round,
            normal,
            elapsed,
            t_m,
            t_c,
            t_p,
        )
        _wait_round_results(client, wave=world.round)
    fault_nodes = client.get_fault_nodes()
    stragglers = client.get_stragglers()
    if stragglers:
        logger.warning("straggler nodes detected: %s", stragglers)
    if config.node_rank in fault_nodes:
        logger.error("this node failed the health check; asking for relaunch")
        return False
    if config.node_rank in stragglers and config.exclude_straggler:
        logger.error("this node is a straggler and exclusion is on")
        return False
    return True


def _wait_round_results(
    client: MasterClient, wave: int = -1, timeout: float = 120.0
) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        resp = client.network_ready(round=wave)
        if resp.ready:
            return
        time.sleep(0.5)
    logger.warning("node check round results incomplete after %.0fs", timeout)


def _comm_perf_report() -> None:
    """--comm-perf-test: measure local-mesh allreduce bus bandwidth once.

    Reference: comm-perf subprocess in trainer/torch/node_check. On a
    real TPU host this exercises ICI; in tests, the XLA CPU ring. The
    result is log-only (operator triage data, not a fault signal).
    """
    import jax
    import jax.numpy as jnp

    try:
        devices = jax.local_devices()
        n = len(devices)
        if n < 2:
            return
        mb = 8
        x = jnp.ones((n, mb * 1024 * 1024 // 4), jnp.float32)
        psum = _local_psum(devices)
        psum(x).block_until_ready()  # compile
        started = time.monotonic()
        psum(x).block_until_ready()
        dt = time.monotonic() - started
        # ring-allreduce bus bandwidth: each device moves 2(n-1)/n of its
        # payload over the interconnect
        bus_gb = (mb / 1024) * 2 * (n - 1) / n
        logger.info(
            "comm perf: %d devices, %.1f MB/device allreduce in %.4fs "
            "(~%.2f GB/s bus)",
            n,
            float(mb),
            dt,
            bus_gb / dt if dt > 0 else 0.0,
        )
    except Exception as e:
        logger.warning("comm perf test failed: %s", e)


def main(argv=None) -> int:
    """Child entry: run the device checks in THIS process and print the
    verdict as one JSON line. The parent agent never touches JAX."""
    argv = sys.argv[1:] if argv is None else argv
    verdict = {
        "matmul": _device_matmul_seconds(),
        "collective": _local_collective_seconds(),
    }
    if "--comm-perf" in argv:
        _comm_perf_report()
    print(json.dumps(verdict), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
