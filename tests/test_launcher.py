"""tpurun launcher: arg parsing, standalone master, node check, e2e run.

Mirrors the reference's launcher tests (dlrover/python/tests/
test_elastic_run.py + trainer/tests/torch/elastic_run_test.py): parse
matrix, master spawn/discovery, and a real standalone end-to-end launch
of a tiny worker script.
"""

import os
import sys
import threading
import time

import pytest

from dlrover_tpu.common.constants import NodeEnv, RendezvousName
from dlrover_tpu.launcher import elastic_run, node_check
from dlrover_tpu.launcher.elastic_run import (
    config_from_args,
    parse_args,
    parse_nnodes,
)
from dlrover_tpu.master.local_master import LocalJobMaster
from dlrover_tpu.rpc.client import MasterClient


@pytest.fixture(autouse=True)
def _clean_client(monkeypatch):
    MasterClient.reset_singleton()
    yield
    MasterClient.reset_singleton()


def test_parse_nnodes():
    assert parse_nnodes("4") == (4, 4)
    assert parse_nnodes("2:8") == (2, 8)


def test_parse_args_full():
    ns = parse_args(
        [
            "--standalone",
            "--nnodes",
            "2:4",
            "--nproc_per_node",
            "8",
            "--node_unit",
            "2",
            "--network-check",
            "--precheck",
            "2",
            "--max_restarts",
            "5",
            "train.py",
            "--lr",
            "3e-4",
        ]
    )
    assert ns.standalone and ns.network_check
    assert ns.precheck == 2
    assert ns.entrypoint == "train.py"
    assert ns.entry_args == ["--lr", "3e-4"]
    config = config_from_args(ns)
    assert (config.min_nodes, config.max_nodes) == (2, 4)
    assert config.local_world_size == 8
    assert config.node_unit == 2
    assert config.max_restarts == 5


def test_parse_args_module():
    ns = parse_args(["-m", "my.pkg.train", "--foo"])
    assert ns.module
    config = config_from_args(ns)
    assert config.run_module
    assert config.entrypoint == "my.pkg.train"


def test_auto_config_from_env(monkeypatch):
    monkeypatch.setenv(NodeEnv.NODE_NUM, "6")
    monkeypatch.setenv(NodeEnv.NODE_UNIT, "3")
    ns = parse_args(["--auto_config", "train.py"])
    config = config_from_args(ns)
    assert (config.min_nodes, config.max_nodes) == (6, 6)
    assert config.node_unit == 3
    assert config.network_check  # ≥4 nodes auto-enables the health check


def test_service_type_propagates_into_worker_env(monkeypatch):
    """Regression: the launcher must carry DLROVER_MASTER_SERVICE_TYPE
    into the worker env contract — worker_env() re-exports the config
    field, and the old grpc default silently pointed every trainer of
    an HTTP-master job at the wrong transport (step reports lost)."""
    monkeypatch.setenv(NodeEnv.MASTER_SERVICE_TYPE, "http")
    config = config_from_args(parse_args(["train.py"]))
    assert config.master_service_type == "http"
    assert config.worker_env()[NodeEnv.MASTER_SERVICE_TYPE] == "http"


def test_wait_pre_check_passes(monkeypatch):
    master = LocalJobMaster(num_workers=1, fresh_context=True)
    master.prepare()
    try:
        monkeypatch.setenv(NodeEnv.MASTER_ADDR, master.addr)
        client = MasterClient.singleton()
        assert elastic_run.wait_pre_check(client, level=2, timeout=10)
    finally:
        master.stop()


def _run_single_node_check(master, monkeypatch, rank=0, num=1):
    monkeypatch.setenv(NodeEnv.MASTER_ADDR, master.addr)
    from dlrover_tpu.agent.config import ElasticLaunchConfig

    client = MasterClient.singleton()
    config = ElasticLaunchConfig(
        min_nodes=num, max_nodes=num, node_rank=rank, node_id=rank
    )
    return node_check.run_node_check(config, client)


def test_node_check_single_node(monkeypatch):
    master = LocalJobMaster(num_workers=1, fresh_context=True)
    master.prepare()
    try:
        assert _run_single_node_check(master, monkeypatch)
    finally:
        master.stop()


def test_node_check_pair_isolates_fault(monkeypatch):
    """Two simulated hosts run the check; the one whose device check fails
    is reported faulty by the master (SURVEY §2.6)."""
    master = LocalJobMaster(num_workers=2, fresh_context=True)
    master.prepare()
    results = {}

    def run_host(rank, healthy):
        from dlrover_tpu.agent.config import ElasticLaunchConfig
        from dlrover_tpu.rpc.client import MasterClient as MC

        client = MC(master_addr=master.addr, node_id=rank)
        config = ElasticLaunchConfig(
            min_nodes=2, max_nodes=2, node_rank=rank, node_id=rank
        )
        # Both hosts run the FULL protocol (including the pair exchange);
        # the faulty one only has its device matmul stubbed to fail.
        matmul_fn = None if healthy else (lambda: (False, 0.0))
        results[rank] = node_check.run_node_check(
            config, client, matmul_fn=matmul_fn
        )

    threads = [
        threading.Thread(target=run_host, args=(0, True)),
        threading.Thread(target=run_host, args=(1, False)),
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert results[0] is True
    assert results[1] is False
    master.stop()


def test_standalone_end_to_end(tmp_path, monkeypatch):
    """Full tpurun standalone launch: spawns a real master subprocess and
    a real worker subprocess, runs to success."""
    script = tmp_path / "train_ok.py"
    script.write_text(
        "import os\n"
        "assert os.environ['DLROVER_COORDINATOR_ADDRESS']\n"
        "assert os.environ['DLROVER_NUM_PROCESSES'] == '1'\n"
        "assert os.environ['DLROVER_PROCESS_ID'] == '0'\n"
        "print('worker ran fine')\n"
    )
    monkeypatch.delenv(NodeEnv.MASTER_ADDR, raising=False)
    monkeypatch.setenv("DLROVER_LOCAL_DEVICES", "1")
    rc = elastic_run.main(
        ["--standalone", "--nnodes", "1", str(script)]
    )
    assert rc == 0


def test_standalone_worker_failure_relaunch_path(tmp_path, monkeypatch):
    """A permanently failing worker exhausts restarts and the launcher
    exits nonzero (asking the platform for a relaunch)."""
    script = tmp_path / "train_bad.py"
    script.write_text("raise SystemExit(3)\n")
    monkeypatch.delenv(NodeEnv.MASTER_ADDR, raising=False)
    monkeypatch.setenv("DLROVER_LOCAL_DEVICES", "1")
    rc = elastic_run.main(
        ["--standalone", "--nnodes", "1", "--max_restarts", "0", str(script)]
    )
    assert rc != 0


def test_node_check_leaves_the_agent_off_the_chip():
    """One process per chip: the device probes run in a CHILD that has
    exited by the time the check returns; the process that ran the check
    (the agent) has no initialized JAX backend, so its worker can still
    take the chip."""
    import subprocess

    code = (
        "import sys\n"
        "from dlrover_tpu.agent.config import ElasticLaunchConfig\n"
        "from dlrover_tpu.launcher import node_check\n"
        "from dlrover_tpu.master.local_master import LocalJobMaster\n"
        "from dlrover_tpu.rpc.client import MasterClient\n"
        "master = LocalJobMaster(num_workers=1, fresh_context=True)\n"
        "master.prepare()\n"
        "client = MasterClient(master_addr=master.addr, node_id=0)\n"
        "config = ElasticLaunchConfig(min_nodes=1, max_nodes=1)\n"
        "ok = node_check.run_node_check(config, client)\n"
        "master.stop()\n"
        "jax = sys.modules.get('jax')\n"
        "backends = dict(jax._src.xla_bridge._backends) if jax else {}\n"
        "print('VERDICT', ok, 'jax' in sys.modules, len(backends))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    (verdict,) = [
        l for l in proc.stdout.splitlines() if l.startswith("VERDICT")
    ]
    _, ok, _jax_imported, n_backends = verdict.split()
    assert ok == "True" and n_backends == "0"


def test_node_check_child_failure_is_a_failed_check(monkeypatch):
    """A probe child that dies (the chip is held, the plugin is broken)
    is a FAILED device check — never a pass, never an exception that
    skips the report to the master."""
    from dlrover_tpu.agent.config import ElasticLaunchConfig

    monkeypatch.setattr(
        node_check.sys, "executable", "/bin/false", raising=False
    )
    verdict = node_check._probe_devices_in_child(ElasticLaunchConfig())
    assert verdict == {"matmul": (False, 0.0), "collective": (False, 0.0)}
