"""NUMA affinity for TPU workers.

Reference: ``--numa-affinity`` (``elastic_run.py:124-217``) backed by
``util/numa_util.py``, which maps each NPU's PCI bus to its NUMA node
and pins the trainer there. TPU shape: v5e/v4 hosts are dual-socket and
the TPU chips hang off ONE socket's PCIe root; a worker scheduled on the
far socket pays cross-socket traffic for every infeed/outfeed DMA. We
read the TPU PCI devices' ``numa_node`` straight from sysfs (vendor
0x1ae0 = Google) and pin the worker to that node's cpulist.

Everything degrades to a no-op: single-NUMA hosts, containers without
sysfs, or devices that are not on PCI simply leave affinity untouched.
"""

import os
from typing import List, Optional, Set

from ..common.log import logger

_PCI_ROOT = "/sys/bus/pci/devices"
_NODE_ROOT = "/sys/devices/system/node"
_GOOGLE_VENDOR = "0x1ae0"


def _read(path: str) -> Optional[str]:
    try:
        with open(path) as f:
            return f.read().strip()
    except OSError:
        return None


def parse_cpulist(text: str) -> List[int]:
    """'0-3,8,10-11' → [0,1,2,3,8,10,11] (sysfs cpulist format)."""
    cpus: List[int] = []
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        if "-" in part:
            lo, _, hi = part.partition("-")
            cpus.extend(range(int(lo), int(hi) + 1))
        else:
            cpus.append(int(part))
    return cpus


def tpu_numa_nodes(pci_root: str = _PCI_ROOT) -> Set[int]:
    """NUMA nodes hosting Google PCI devices (TPU chips). Empty when
    none are visible (no sysfs, CPU host)."""
    nodes: Set[int] = set()
    try:
        devices = os.listdir(pci_root)
    except OSError:
        return nodes
    for dev in devices:
        base = os.path.join(pci_root, dev)
        if _read(os.path.join(base, "vendor")) != _GOOGLE_VENDOR:
            continue
        raw = _read(os.path.join(base, "numa_node"))
        if raw is None:
            continue
        try:
            node = int(raw)
        except ValueError:
            continue
        if node >= 0:  # -1 = unknown/single-node
            nodes.add(node)
    return nodes


def numa_cpus(node: int, node_root: str = _NODE_ROOT) -> List[int]:
    raw = _read(os.path.join(node_root, f"node{node}", "cpulist"))
    return parse_cpulist(raw) if raw else []


def tpu_numa_cpuset(
    pci_root: str = _PCI_ROOT, node_root: str = _NODE_ROOT
) -> Optional[Set[int]]:
    """CPU set of the TPU-local NUMA node(s), or None when topology is
    invisible. Safe to call (and log) in the PARENT; the spawn path
    passes the result to a logging-free ``sched_setaffinity`` in the
    child's preexec (logging between fork and exec can deadlock on a
    lock held at fork time)."""
    nodes = tpu_numa_nodes(pci_root)
    if not nodes:
        logger.info("numa affinity: no TPU PCI devices visible; skipping")
        return None
    cpus: Set[int] = set()
    for node in nodes:
        cpus.update(numa_cpus(node, node_root))
    if not cpus:
        logger.info("numa affinity: no cpulist for nodes %s; skipping", nodes)
        return None
    logger.info(
        "numa affinity: node(s) %s (%d cpus)", sorted(nodes), len(cpus)
    )
    return cpus


def numa_preexec(pci_root: str = _PCI_ROOT, node_root: str = _NODE_ROOT):
    """Spawn-path helper: compute (and log) the TPU-local cpu set in the
    PARENT, return a logging-free callable for ``subprocess.Popen``'s
    ``preexec_fn`` — or None when there is nothing to pin. Threads the
    child spawns later inherit the mask, which pinning a live pid after
    the fact cannot guarantee."""
    cpus = tpu_numa_cpuset(pci_root, node_root)
    if not cpus:
        return None
    return lambda: os.sched_setaffinity(0, cpus)


def apply_numa_affinity(
    pid: int = 0,
    pci_root: str = _PCI_ROOT,
    node_root: str = _NODE_ROOT,
) -> Optional[Set[int]]:
    """Pin ``pid`` to the CPUs of the TPU-local NUMA node(s). Returns
    the applied CPU set, or None when nothing was done (no TPU PCI
    devices visible, unknown topology, or sched_setaffinity denied).
    NOTE: pinning an already-running pid covers only its main thread —
    spawn paths should use ``tpu_numa_cpuset`` + preexec instead."""
    cpus = tpu_numa_cpuset(pci_root, node_root)
    if not cpus:
        return None
    try:
        os.sched_setaffinity(pid, cpus)
    except (OSError, AttributeError) as e:
        logger.warning("numa affinity failed: %s", e)
        return None
    return cpus
